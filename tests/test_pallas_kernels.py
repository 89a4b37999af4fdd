"""The PR 9 Pallas kernel campaign (pallas_kernels/batchnorm_fused.py,
optimizer_apply.py, quantized_matmul.py) in interpreter mode on CPU.

Parity contracts under test (same as BENCH_MODEL=fused_kernels):
- fused BatchNorm: bitwise-equal stats AND output vs its reference
  (the deterministic tree/exact-product design makes even the
  normalize chain reproducible across fusion contexts and tilings),
  custom_vjp grads vs reference autodiff, fits-guard fallback, and the
  gluon.nn.BatchNorm moving-stats round-trip through save/load.
- packed optimizer apply: BITWISE-equal to the per-parameter step_fn
  chain inside one jit for SGD/momentum/Adam, on both the flat jnp
  path and the interpret-mode kernel; the fused train step produces
  bit-identical parameters with MXTPU_FUSED_APPLY=0/1/interpret.
- quantized matmul: int32 accumulator exactly equal to the XLA dot
  (integer math is exact), f32 scaled epilogue within 1 ULP, and the
  ops/quantized.py wiring (FC + 1x1 conv) bitwise across paths.
The real-TPU speedup half of the contract lives in bench.py
(BENCH_MODEL=fused_kernels, >=1.5x where a real backend is present).
"""
import importlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

BN = importlib.import_module("mxnet_tpu.pallas_kernels.batchnorm_fused")
OA = importlib.import_module("mxnet_tpu.pallas_kernels.optimizer_apply")
QM = importlib.import_module("mxnet_tpu.pallas_kernels.quantized_matmul")


def _bn_mats(N, H, W, C, dtype="float32", seed=0):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(N, H, W, C).astype("float32") * 2 + 1) \
        .astype(dtype)
    g = jnp.asarray(rs.rand(C).astype("float32") + 0.5)
    b = jnp.asarray(rs.randn(C).astype("float32"))
    return x, g, b


def _eq(a, b):
    return bool(jnp.array_equal(jnp.asarray(a), jnp.asarray(b),
                                equal_nan=True))


def _within_ulps(a, b, n=2):
    """|a - b| <= n float32 ulp OF THE LARGEST MAGNITUDE in ``b``. For a
    chain ``p * q + r`` that one program contracts to a fused multiply-add
    (one rounding) and the other does not (two): XLA's CPU backend under
    JAX 0.9.0 decides that per fusion, so two programs of the same
    arithmetic differ by an ulp of the product, however small the sum
    after cancellation. Not a tolerance for a wrong reduction order: the
    statistics beside it stay bitwise."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return bool(np.abs(a - b).max() <= n * np.spacing(np.abs(b).max()))


# ---------------------------------------------------------------------------
# deterministic reduction primitives
# ---------------------------------------------------------------------------

class TestDeterministicReduction:
    def test_tree_fold_jit_eager_bitwise(self):
        """The whole point of the fold: the same bits from any
        compilation context."""
        rs = np.random.RandomState(1)
        v = jnp.asarray(rs.randn(333, 24).astype("float32"))
        assert _eq(BN.tree_fold_rows(v),
                   jax.jit(BN.tree_fold_rows)(v))

    def test_tree_fold_is_the_sum(self):
        rs = np.random.RandomState(2)
        v = jnp.asarray(rs.randn(100, 8).astype("float32"))
        np.testing.assert_allclose(
            np.asarray(BN.tree_fold_rows(v)[0]),
            np.asarray(v).sum(0), rtol=1e-6)

    def test_tile_decomposition_matches_full_tree(self):
        """fold_partials(concat(per-tile fold_blocks)) == full tree for
        any FOLD_BLOCK-aligned tiling — the property that makes the
        stats kernel's tiled partials bitwise-equal to the
        reference."""
        rs = np.random.RandomState(3)
        v = jnp.asarray(rs.randn(256, 16).astype("float32"))
        full = BN.tree_fold_rows(v)
        for tr in (64, 128):
            parts = jnp.concatenate(
                [BN.fold_blocks(v[i:i + tr])
                 for i in range(0, 256, tr)], axis=0)
            assert _eq(BN.fold_partials(parts), full), tr

    def test_exact_sq_and_mul(self):
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(1000).astype("float32") * 100)
        y = jnp.asarray(rs.randn(1000).astype("float32"))
        np.testing.assert_allclose(np.asarray(BN.exact_sq(x)),
                                   np.asarray(x) ** 2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(BN.exact_mul(x, y)),
                                   np.asarray(x) * np.asarray(y),
                                   rtol=1e-6)
        # context-independence: jit == eager bitwise
        assert _eq(BN.exact_sq(x), jax.jit(BN.exact_sq)(x))
        assert _eq(BN.exact_mul(x, y), jax.jit(BN.exact_mul)(x, y))
        # non-finite mirror plain multiply
        sp = jnp.asarray(np.array([np.inf, -np.inf, np.nan, 0.0],
                                  "float32"))
        assert _eq(BN.exact_sq(sp), sp * sp)


# ---------------------------------------------------------------------------
# fused BatchNorm
# ---------------------------------------------------------------------------

class TestBatchNormFused:
    @pytest.mark.parametrize("shape", [(4, 6, 6, 16), (2, 8, 8, 32)])
    @pytest.mark.parametrize("act", [None, "relu"])
    def test_forward_bitwise_vs_reference(self, shape, act):
        x, g, b = _bn_mats(*shape)
        k = jax.jit(lambda *a: BN.fused_batch_norm(
            *a, act=act, interpret=True))(x, g, b)
        r = jax.jit(lambda *a: BN.batchnorm_reference(*a, act=act))(
            x, g, b)
        for a, c in zip(k, r):
            assert _eq(a, c)

    def test_multi_tile_matches_reference(self, monkeypatch):
        """Force a 4-row-tile x 2-channel-tile grid: the per-tile
        partials must reassemble into the exact reference tree."""
        monkeypatch.setattr(BN, "_tiles",
                            lambda R, C, xb, nb: (64, 16, True))
        x, g, b = _bn_mats(4, 8, 8, 32)  # R=256 -> 4 row tiles
        out, mean, var = BN.fused_batch_norm(x, g, b, interpret=True)
        r_out, r_mean, r_var = BN.batchnorm_reference(x, g, b)
        # the reassembled partials, bitwise; the normalize chain
        # (x - mean) * inv * g + b to 2 ulp of the largest output: under
        # the forced tiling its multiply-adds fuse differently (read here:
        # 1077 of 8192 outputs off by at most 4.77e-7 = 1 ulp of 4.84)
        assert _eq(mean, r_mean) and _eq(var, r_var)
        assert _within_ulps(out, r_out)

    def test_bf16_stats_in_f32(self):
        x, g, b = _bn_mats(2, 4, 4, 16, dtype="bfloat16")
        out, mean, var = BN.fused_batch_norm(x, g, b, interpret=True)
        assert out.dtype == jnp.bfloat16
        assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
        _, rm, rv = BN.batchnorm_reference(x, g, b)
        assert _eq(mean, rm) and _eq(var, rv)

    def test_gradients_match_reference(self):
        # act="relu" covers the mask recomputation ON TOP of the base
        # backward; the shape matches test_forward so the interpret
        # kernels compile once per suite run
        act = "relu"
        x, g, b = _bn_mats(4, 6, 6, 16, seed=7)

        def lk(x, g, b):
            return jnp.sum(BN.fused_batch_norm(
                x, g, b, act=act, interpret=True)[0] ** 2)

        def lr(x, g, b):
            return jnp.sum(BN.batchnorm_reference(x, g, b, act=act)[0]
                           ** 2)

        gk = jax.grad(lk, argnums=(0, 1, 2))(x, g, b)
        gr = jax.grad(lr, argnums=(0, 1, 2))(x, g, b)
        for a, c in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-4, rtol=2e-4)

    def test_stat_output_cotangents(self):
        """Differentiating through the mean/var OUTPUTS must match the
        reference autodiff (the custom_vjp adds the d mean/dx and
        d var/dx terms explicitly)."""
        x, g, b = _bn_mats(4, 6, 6, 16, seed=9)

        def lk(x):
            o, m, v = BN.fused_batch_norm(x, g, b, interpret=True)
            return jnp.sum(m * 3.0) + jnp.sum(v * 0.5)

        def lr(x):
            o, m, v = BN.batchnorm_reference(x, g, b)
            return jnp.sum(m * 3.0) + jnp.sum(v * 0.5)

        np.testing.assert_allclose(np.asarray(jax.grad(lk)(x)),
                                   np.asarray(jax.grad(lr)(x)),
                                   atol=1e-5, rtol=1e-5)

    def test_fits_guard_falls_back_to_reference(self, monkeypatch):
        """An unfittable plan must take batchnorm_reference instead of
        dying at Mosaic compile time (conv_fused contract)."""
        called = []
        real = BN.batchnorm_reference
        monkeypatch.setattr(BN, "_use_pallas", lambda *a, **k: True)
        monkeypatch.setattr(BN, "_fwd_fits", lambda x2: False)
        monkeypatch.setattr(
            BN, "batchnorm_reference",
            lambda *a, **k: called.append(1) or real(*a, **k))
        x, g, b = _bn_mats(2, 4, 4, 8)
        out = BN.fused_batch_norm(x, g, b)
        assert called, "unfittable plan did not fall back"
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(real(x, g, b)[0]))

    def test_engaged_gates(self, monkeypatch):
        x, g, b = _bn_mats(2, 4, 4, 8)
        monkeypatch.setenv("MXTPU_FUSED_BN", "0")
        assert not BN.engaged(x, 3)
        monkeypatch.setenv("MXTPU_FUSED_BN", "interpret")
        assert BN.engaged(x, 3)
        assert not BN.engaged(x, 1)  # channels not last

    def test_shape_validation(self):
        x, g, b = _bn_mats(2, 4, 4, 8)
        with pytest.raises(ValueError):
            BN.fused_batch_norm(x, g[:4], b, interpret=True)
        with pytest.raises(ValueError):
            BN.fused_batch_norm(x, g, b, act="gelu", interpret=True)


class TestBatchNormGluon:
    """ops/nn.py wiring + gluon.nn.BatchNorm semantics with the kernel
    engaged via the MXTPU_FUSED_BN=interpret CPU hook."""

    def _train(self, monkeypatch, tmp_path, mode, steps=2):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon
        monkeypatch.setenv("MXTPU_FUSED_BN", mode)
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.BatchNorm(axis=1, in_channels=16, momentum=0.8)
        net.initialize()
        rs = np.random.RandomState(1)
        for i in range(steps):
            x = mx.nd.array(rs.rand(32, 16).astype("float32") + i)
            with autograd.record():
                y = net(x)
            y.backward()
        return net, y

    def test_moving_stats_roundtrip_save_load(self, monkeypatch,
                                              tmp_path):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, gluon
        net, _ = self._train(monkeypatch, tmp_path, "interpret")
        rm = net.running_mean.data().asnumpy()
        rv = net.running_var.data().asnumpy()
        assert not np.allclose(rm, 0.0)  # stats actually moved
        path = str(tmp_path / "bn.params")
        net.save_parameters(path)
        net2 = gluon.nn.BatchNorm(axis=1, in_channels=16, momentum=0.8)
        net2.load_parameters(path)
        np.testing.assert_array_equal(
            rm, net2.running_mean.data().asnumpy())
        np.testing.assert_array_equal(
            rv, net2.running_var.data().asnumpy())
        # inference after reload uses the restored moving stats
        x = mx.nd.array(np.random.RandomState(5).rand(8, 16)
                        .astype("float32"))
        with autograd.pause():
            y1 = net(x).asnumpy()
            y2 = net2(x).asnumpy()
        np.testing.assert_array_equal(y1, y2)

    def test_kernel_vs_fallback_stats_agree(self, monkeypatch,
                                            tmp_path):
        """Running stats through the kernel path track the fallback's
        within f32 stat noise (different variance pass structure:
        single- vs two-pass)."""
        net_k, yk = self._train(monkeypatch, tmp_path, "interpret")
        net_f, yf = self._train(monkeypatch, tmp_path, "0")
        np.testing.assert_allclose(
            net_k.running_mean.data().asnumpy(),
            net_f.running_mean.data().asnumpy(), atol=1e-6)
        np.testing.assert_allclose(
            net_k.running_var.data().asnumpy(),
            net_f.running_var.data().asnumpy(), atol=1e-5)
        # outputs amplify the single- vs two-pass var gap through
        # 1/sqrt; f32-noise-level agreement, not bitwise
        np.testing.assert_allclose(yk.asnumpy(), yf.asnumpy(),
                                   atol=1e-4, rtol=1e-5)

    def test_env_flip_invalidates_dispatch_cache(self, monkeypatch):
        """MXTPU_FUSED_BN is part of the imperative dispatch-cache key
        (register._kernel_env_token): flipping it mid-process on an
        already-hot signature must retrace onto the other path, never
        silently replay the cached program."""
        import mxnet_tpu as mx
        from mxnet_tpu.ops import nn as opsnn
        monkeypatch.setenv("MXTPU_FUSED_BN", "interpret")
        rs = np.random.RandomState(0)
        args = [mx.nd.array(a) for a in (
            rs.rand(16, 24).astype("float32"), rs.rand(24),
            rs.rand(24), rs.rand(24), rs.rand(24) + 0.5)]
        # training-mode call: the path the env var actually routes
        kw = dict(eps=1e-3, fix_gamma=False, axis=1, _training=True)
        for _ in range(3):  # past the compile-on-repeat threshold
            out_k = mx.nd.BatchNorm(*args, **kw)[0].asnumpy()
        calls = []
        orig = opsnn.batch_moments
        monkeypatch.setattr(
            opsnn, "batch_moments",
            lambda *a, **k: calls.append(1) or orig(*a, **k))
        mx.nd.BatchNorm(*args, **kw)[0].asnumpy()
        assert not calls  # cache hit: no retrace on the hot signature
        monkeypatch.setenv("MXTPU_FUSED_BN", "0")
        out_f = mx.nd.BatchNorm(*args, **kw)[0].asnumpy()
        assert calls, "env flip did not retrace — cached kernel " \
            "program silently replayed"
        np.testing.assert_allclose(out_k, out_f, atol=1e-4, rtol=1e-5)

    def test_use_global_stats_keeps_fallback(self, monkeypatch):
        """Inference / use_global_stats never routes to the kernel
        (its contract is training-mode batch stats)."""
        from mxnet_tpu.ops import nn as opsnn
        monkeypatch.setenv("MXTPU_FUSED_BN", "interpret")
        called = []
        orig = BN.fused_batch_norm
        monkeypatch.setattr(BN, "fused_batch_norm",
                            lambda *a, **k: called.append(1) or
                            orig(*a, **k))
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.rand(8, 16).astype("float32"))
        g = jnp.asarray(rs.rand(16).astype("float32"))
        b = jnp.asarray(rs.rand(16).astype("float32"))
        mm = jnp.asarray(rs.rand(16).astype("float32"))
        mv = jnp.asarray(rs.rand(16).astype("float32") + 0.5)
        opsnn.batch_norm(x, g, b, mm, mv, axis=1, _training=False)
        opsnn.batch_norm(x, g, b, mm, mv, axis=1,
                         use_global_stats=True, _training=True)
        assert not called
        opsnn.batch_norm(x, g, b, mm, mv, axis=1, _training=True)
        assert called


class TestBatchNormFallbackNumerics:
    """The PR 9 satellite: the XLA-fallback batch_norm computes stats
    in f32 (never rounded to the input dtype before the inverse) and
    the whole op is bitwise-deterministic across compilation contexts
    — the properties behind dropping the per-op ULP budget from the
    11,482 BENCH_r05 measured to 64."""

    def test_output_bitwise_across_contexts(self):
        """jit vs eager == 0 ULP: reduction order is pinned by the
        tree and FMA contraction is neutralized by exact products, so
        no fusion context can move a single output bit — the
        regression guard for the 11,482-ULP class of drift."""
        from mxnet_tpu.ops.nn import batch_norm
        rs = np.random.RandomState(0)
        args = [jnp.asarray(a) for a in (
            rs.rand(8, 16, 8, 8).astype("float32"), rs.rand(16),
            rs.rand(16), rs.rand(16), rs.rand(16) + 0.5)]
        args = [a.astype(jnp.float32) for a in args]
        for kw in (dict(_training=True), dict(_training=False),
                   dict(_training=True, use_global_stats=True)):
            kw = dict(eps=1e-3, fix_gamma=False, axis=1, **kw)
            e = batch_norm(*args, **kw)
            j = jax.jit(lambda *a: batch_norm(*a, **kw))(*args)
            for a, c in zip(e, j):
                assert _eq(a, c), kw

    def test_half_precision_stats_accumulate_in_f32(self):
        """bf16 input: batch_moments' f32 stats land within f32 noise
        of the f64 truth — rounding them through bf16 (the old
        input-dtype accumulation bug) would be ~2^8 times coarser."""
        from mxnet_tpu.ops.nn import batch_moments
        rs = np.random.RandomState(3)
        x64 = rs.rand(64, 24).astype(np.float64) * 2 + 3
        x = jnp.asarray(x64.astype("float32")).astype(jnp.bfloat16)
        x64 = np.asarray(x, np.float64)  # the values the op really saw
        m32, v32 = batch_moments(x, (0,), axis=1, fp32_out=True)
        assert m32.dtype == jnp.float32 and v32.dtype == jnp.float32
        m_true = x64.mean(0)
        v_true = ((x64 - m_true) ** 2).mean(0)
        # f32-level agreement (~1e-7 rel); bf16-rounded stats would be
        # off by ~1e-2 rel on these magnitudes
        np.testing.assert_allclose(np.asarray(m32), m_true, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(v32), v_true, rtol=1e-3)
        bf16_err = np.abs(
            np.asarray(m32.astype(jnp.bfloat16), np.float64) - m_true)
        f32_err = np.abs(np.asarray(m32, np.float64) - m_true)
        assert f32_err.max() < bf16_err.max() / 16

    def test_half_precision_output_uses_f32_stats(self):
        """The normalize chain runs off the f32 stats: the bf16 output
        must match an all-f64 reference to within bf16 OUTPUT rounding
        (the old path added bf16 STAT rounding on top, visibly
        shifting outputs near the mean)."""
        from mxnet_tpu.ops.nn import batch_norm
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.rand(64, 24).astype("float32") * 2 + 3) \
            .astype(jnp.bfloat16)
        g = jnp.asarray(rs.rand(24).astype("float32") + 0.5)
        b = jnp.asarray(rs.rand(24).astype("float32"))
        out = batch_norm(x, g, b, jnp.zeros(24), jnp.ones(24),
                         eps=1e-5, fix_gamma=False, axis=1,
                         _training=True)[0]
        x64 = np.asarray(x, np.float64)
        m = x64.mean(0)
        v = ((x64 - m) ** 2).mean(0)
        ref = (x64 - m) / np.sqrt(v + 1e-5) * np.asarray(g, np.float64) \
            + np.asarray(b, np.float64)
        assert np.abs(np.asarray(out, np.float64) - ref).max() < 0.02


# ---------------------------------------------------------------------------
# packed optimizer apply
# ---------------------------------------------------------------------------

def _opt_cases():
    from mxnet_tpu.optimizer.optimizer import SGD, Adam
    shapes = [(64, 32), (32,), (32, 16), (16,), (7, 3)]
    rs = np.random.RandomState(0)
    ws = [jnp.asarray(rs.randn(*s).astype("float32")) for s in shapes]
    gs = [jnp.asarray(rs.randn(*s).astype("float32")) for s in shapes]
    return [
        ("sgd_momentum", SGD(momentum=0.9, learning_rate=0.05, wd=1e-4),
         ws, gs, [jnp.zeros_like(w) for w in ws]),
        ("sgd", SGD(momentum=0.0, learning_rate=0.05), ws, gs,
         [None] * len(ws)),
        ("adam", Adam(learning_rate=1e-3), ws, gs,
         [(jnp.asarray(rs.rand(*s).astype("float32") * 0.1),
           jnp.asarray(rs.rand(*s).astype("float32") * 0.01))
          for s in shapes]),
    ]


class TestOptimizerApply:
    @pytest.mark.parametrize("case", _opt_cases(),
                             ids=lambda c: c[0])
    @pytest.mark.parametrize("interp", [False, True],
                             ids=["flat", "interpret"])
    def test_bitwise_vs_per_param_in_jit(self, case, interp):
        _, opt, ws, gs, states = case
        lrs = [jnp.float32(0.05 + 0.01 * i) for i in range(len(ws))]
        wds = [jnp.float32(1e-4 * i) for i in range(len(ws))]
        rescale = jnp.float32(1.0 / 32)

        def perparam(ws, gs, states, lrs, wds, rescale):
            outs = [opt.step_fn(w, g, st, lr, wd, rescale)
                    for w, g, st, lr, wd in zip(ws, gs, states, lrs,
                                                wds)]
            return [o[0] for o in outs], [o[1] for o in outs]

        def packed(ws, gs, states, lrs, wds, rescale):
            return OA.packed_apply(opt, ws, gs, states, lrs, wds,
                                   rescale, interpret=interp)

        r_pp = jax.jit(perparam)(ws, gs, states, lrs, wds, rescale)
        r_pk = jax.jit(packed)(ws, gs, states, lrs, wds, rescale)
        # bitwise, but for the momentum through the interpreted kernel:
        # 0.9 * m - lr * (g * rescale + wd * w) contracts to fused
        # multiply-adds there and not in the per-parameter chain (read
        # here: the weights equal, 7 of 1194 momentum entries off by at
        # most 5.8e-11 where the leaf's largest is 5e-3, ulp 4.7e-10)
        same = _within_ulps if (case[0], interp) == ("sgd_momentum", True) \
            else _eq
        for a, c in zip(r_pp[0], r_pk[0]):
            assert _eq(a, c)
        for a, c in zip(jax.tree_util.tree_leaves(r_pp[1]),
                        jax.tree_util.tree_leaves(r_pk[1])):
            assert same(a, c)

    def test_bucketize_is_bucket_plan(self):
        """ONE shared packing definition: the kernel segments are the
        wire-reduction buckets (parallel/overlap.bucket_plan)."""
        from mxnet_tpu.parallel.overlap import bucket_plan
        rs = np.random.RandomState(0)
        ws = [jnp.asarray(rs.randn(8, 8).astype(d))
              for d in ("float32", "float32", "bfloat16", "float32")]
        assert OA.bucketize(ws) == bucket_plan(ws)
        # dtype change splits the bucket
        assert len(OA.bucketize(ws)) >= 2

    def test_mixed_dtype_buckets(self):
        from mxnet_tpu.optimizer.optimizer import SGD
        opt = SGD(momentum=0.9, learning_rate=0.05)
        rs = np.random.RandomState(0)
        ws = [jnp.asarray(rs.randn(16, 8).astype("float32")),
              jnp.asarray(rs.randn(8,).astype("bfloat16")),
              jnp.asarray(rs.randn(4, 4).astype("float32"))]
        gs = [jnp.asarray(rs.randn(*w.shape).astype(str(w.dtype)))
              for w in ws]
        states = [jnp.zeros_like(w) for w in ws]
        lrs = [jnp.float32(0.05)] * 3
        wds = [jnp.float32(1e-4)] * 3
        rescale = jnp.float32(1.0)

        def perparam():
            outs = []
            for w, g, st, lr, wd in zip(ws, gs, states, lrs, wds):
                if w.dtype != jnp.float32:
                    lr = lr.astype(w.dtype)
                    wd = wd.astype(w.dtype)
                    rs_ = rescale.astype(w.dtype)
                else:
                    rs_ = rescale
                outs.append(opt.step_fn(w, g, st, lr, wd, rs_))
            return [o[0] for o in outs], [o[1] for o in outs]

        def packed():
            return OA.packed_apply(opt, ws, gs, states, lrs, wds,
                                   rescale, interpret=True)

        r_pp = jax.jit(perparam)()
        r_pk = jax.jit(packed)()
        for a, c in zip(jax.tree_util.tree_leaves(r_pp),
                        jax.tree_util.tree_leaves(r_pk)):
            assert _eq(a, c)

    def test_fused_apply_supported_flags(self):
        from mxnet_tpu.optimizer.optimizer import (SGD, Adam, RMSProp,
                                                   Optimizer)
        assert SGD().fused_apply_supported()
        assert Adam().fused_apply_supported()
        assert not RMSProp().fused_apply_supported()
        assert not Optimizer.fused_apply_supported(Optimizer())


class TestFusedStepApply:
    def _train(self, mode, monkeypatch, optimizer="sgd",
               opt_kwargs=None):
        import random

        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        monkeypatch.setenv("MXTPU_FUSED_APPLY", mode)
        random.seed(0)
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(16, in_units=8, activation="relu"))
            net.add(gluon.nn.Dense(1, in_units=16))
        net.initialize(mx.init.Uniform(0.1))
        net.hybridize()
        tr = gluon.Trainer(net.collect_params(), optimizer,
                           opt_kwargs or {"learning_rate": 0.05,
                                          "momentum": 0.9})
        step = gluon.train_step(net, gluon.loss.L2Loss(), tr)
        rs = np.random.RandomState(0)
        x = mx.nd.array(rs.rand(8, 8).astype("float32"))
        y = mx.nd.array(rs.rand(8, 1).astype("float32"))
        for _ in range(3):  # warm, compile, one fused hit
            step(x, y, batch_size=8)
        assert step.last_mode == "fused", step.last_mode
        return [p.data().asnumpy()
                for _, p in sorted(net.collect_params().items())]

    @pytest.mark.parametrize("optimizer,kwargs", [
        ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
        ("adam", {"learning_rate": 0.001}),
    ])
    def test_train_step_bitwise_across_apply_modes(self, monkeypatch,
                                                   optimizer, kwargs):
        base = self._train("0", monkeypatch, optimizer, kwargs)
        for mode in ("1", "interpret"):
            got = self._train(mode, monkeypatch, optimizer, kwargs)
            for a, c in zip(base, got):
                np.testing.assert_array_equal(a, c)

    def test_unsupported_optimizer_stays_per_param(self, monkeypatch):
        """rmsprop has no packed form — MXTPU_FUSED_APPLY=1 must not
        change its fused-step results (selector returns None)."""
        base = self._train("0", monkeypatch, "rmsprop",
                           {"learning_rate": 0.01})
        got = self._train("1", monkeypatch, "rmsprop",
                          {"learning_rate": 0.01})
        for a, c in zip(base, got):
            np.testing.assert_array_equal(a, c)


# ---------------------------------------------------------------------------
# quantized matmul
# ---------------------------------------------------------------------------

class TestQuantizedMatmul:
    def _ints(self, m, k, n, seed=0):
        rs = np.random.RandomState(seed)
        x = jnp.asarray(rs.randint(-127, 128, (m, k)).astype("int8"))
        w = jnp.asarray(rs.randint(-127, 128, (k, n)).astype("int8"))
        return x, w

    @pytest.mark.parametrize("shape", [(32, 64, 48),    # single tile
                                       (256, 256, 256)])  # tiled grid
    def test_int32_accumulator_exact(self, shape):
        x, w = self._ints(*shape)
        acc = QM.quantized_matmul(x, w, interpret=True)
        assert acc.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(acc),
            np.asarray(QM.quantized_matmul_reference(x, w)))

    def test_scaled_epilogue(self):
        x, w = self._ints(32, 64, 48)
        s = jnp.asarray(np.random.RandomState(1).rand(48)
                        .astype("float32") * 0.01)
        out = QM.quantized_matmul(x, w, scales=s, interpret=True)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(QM.quantized_matmul_reference(x, w, scales=s)))

    def test_fits_guard_falls_back(self, monkeypatch):
        called = []
        real = QM.quantized_matmul_reference
        monkeypatch.setattr(QM, "_use_pallas", lambda *a, **k: True)
        monkeypatch.setattr(QM, "_fits", lambda m, k, n: False)
        monkeypatch.setattr(
            QM, "quantized_matmul_reference",
            lambda *a, **k: called.append(1) or real(*a, **k))
        x, w = self._ints(8, 32, 16)
        out = QM.quantized_matmul(x, w)
        assert called
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(real(x, w)))

    def test_engaged_requires_int8(self, monkeypatch):
        monkeypatch.setenv("MXTPU_QUANT_MATMUL", "interpret")
        x, w = self._ints(8, 32, 16)
        assert QM.engaged(x, w)
        assert not QM.engaged(x.astype(jnp.int32), w)
        monkeypatch.setenv("MXTPU_QUANT_MATMUL", "0")
        assert not QM.engaged(x, w)

    def test_fc_and_conv1x1_wiring(self, monkeypatch):
        """ops/quantized.py routes FC and 1x1 convs through the kernel
        bitwise-identically to the XLA int32 path."""
        from mxnet_tpu.ops.registry import get_op
        rs = np.random.RandomState(0)
        fc = get_op("quantized_fully_connected").fn
        conv = get_op("quantized_conv").fn
        x = jnp.asarray(rs.randint(-127, 128, (8, 64)).astype("int8"))
        w = jnp.asarray(rs.randint(-127, 128, (16, 64)).astype("int8"))
        xc = jnp.asarray(rs.randint(-127, 128, (2, 32, 5, 5))
                         .astype("int8"))
        wc = jnp.asarray(rs.randint(-127, 128, (16, 32, 1, 1))
                         .astype("int8"))
        outs = {}
        for mode in ("interpret", "0"):
            monkeypatch.setenv("MXTPU_QUANT_MATMUL", mode)
            outs[mode] = (
                fc(x, w, None, -1.0, 1.0, -0.5, 0.5, None, None,
                   num_hidden=16, no_bias=True)[0],
                conv(xc, wc, None, -1.0, 1.0, -0.5, 0.5, None, None,
                     kernel=(1, 1), num_filter=16, no_bias=True)[0])
        np.testing.assert_array_equal(np.asarray(outs["interpret"][0]),
                                      np.asarray(outs["0"][0]))
        np.testing.assert_array_equal(np.asarray(outs["interpret"][1]),
                                      np.asarray(outs["0"][1]))

    def test_shape_validation(self):
        x, w = self._ints(8, 32, 16)
        with pytest.raises(ValueError):
            QM.quantized_matmul(x, w.T)


# ---------------------------------------------------------------------------
# compile attribution (ISSUE 8c)
# ---------------------------------------------------------------------------

def test_kernel_compiles_are_attributed():
    """First build per kernel signature lands in
    profiler.compile_stats() under pallas:<kernel> — the Compile table
    entry OBSERVABILITY.md documents."""
    from mxnet_tpu import profiler
    x, g, b = _bn_mats(2, 4, 4, 128, seed=11)
    BN.fused_batch_norm(x, g, b, interpret=True)
    stats = profiler.compile_stats()
    assert any(k.startswith("pallas:batchnorm_fused") for k in stats), \
        sorted(stats)
    entry = stats["pallas:batchnorm_fused.stats"]
    assert entry["count"] >= 1 and entry["total_us"] > 0

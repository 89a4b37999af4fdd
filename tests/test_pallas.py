"""Pallas kernel tests — run under interpret mode on the CPU test platform
(ref slot: src/common/rtc.cc custom-kernel tests, tests/python/gpu/test_rtc.py;
gradient compression: tests/nightly/test_kvstore.py compression cases)."""
import importlib

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.pallas_kernels import (flash_attention, quantize_2bit,
                                      dequantize_2bit, quantize_2bit_jnp,
                                      dequantize_2bit_jnp)
from mxnet_tpu.pallas_kernels.flash_attention import attention_reference


def _qkv(b=2, h=4, s=256, d=64, seed=0):
    rng = onp.random.RandomState(seed)
    return (jnp.array(rng.randn(b, h, s, d).astype("float32")),
            jnp.array(rng.randn(b, h, s, d).astype("float32")),
            jnp.array(rng.randn(b, h, s, d).astype("float32")))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        assert float(jnp.abs(out - ref).max()) < 1e-5

    def test_block_sizes_equivalent(self):
        q, k, v = _qkv(s=128)
        ref = attention_reference(q, k, v)
        for bq, bk in [(128, 128), (64, 128), (128, 64), (32, 32)]:
            out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True)
            assert float(jnp.abs(out - ref).max()) < 1e-5, (bq, bk)

    def test_gradients(self):
        q, k, v = _qkv(s=128)
        g = jax.grad(lambda a, b, c: flash_attention(
            a, b, c, causal=True, interpret=True).sum(), (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: attention_reference(
            a, b, c, causal=True).sum(), (0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            assert float(jnp.abs(got - want).max()) < 1e-4

    def test_cross_attention_lengths(self):
        q, _, _ = _qkv(s=128)
        _, k, v = _qkv(s=256, seed=1)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        assert out.shape == (2, 4, 128, 64)
        assert float(jnp.abs(out - ref).max()) < 1e-5

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_reference(self, causal):
        """The Pallas dq/dk/dv kernels (flash-2 recompute) vs the XLA vjp
        of the dense reference."""
        q, k, v = _qkv(s=128)
        g = jax.grad(lambda a, b, c: (flash_attention(
            a, b, c, causal=causal, block_q=64, block_k=32,
            interpret=True) ** 2).sum(), (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: (attention_reference(
            a, b, c, causal=causal) ** 2).sum(), (0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            assert float(jnp.abs(got - want).max()) < 1e-3

    def test_backward_cross_attention(self):
        q, _, _ = _qkv(s=64)
        _, k, v = _qkv(s=128, seed=1)
        g = jax.grad(lambda a, b, c: flash_attention(
            a, b, c, block_q=32, block_k=64, interpret=True).sum(),
            (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: attention_reference(
            a, b, c).sum(), (0, 1, 2))(q, k, v)
        assert g[0].shape == q.shape and g[1].shape == k.shape
        for got, want in zip(g, gr):
            assert float(jnp.abs(got - want).max()) < 1e-3

    def test_backward_bf16(self):
        q, k, v = _qkv(s=128)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        g = jax.grad(lambda a, b, c: flash_attention(
            a, b, c, causal=True, interpret=True).astype(
                jnp.float32).sum(), (0, 1, 2))(qb, kb, vb)
        gr = jax.grad(lambda a, b, c: attention_reference(
            a, b, c, causal=True).sum(), (0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            assert got.dtype == jnp.bfloat16
            err = jnp.abs(got.astype(jnp.float32) - want).max()
            assert float(err) < 0.2  # bf16 has ~3 decimal digits

    def test_jittable(self):
        q, k, v = _qkv(s=128)
        f = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                    interpret=True))
        ref = attention_reference(q, k, v, causal=True)
        assert float(jnp.abs(f(q, k, v) - ref).max()) < 1e-5


FA = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")


def _fwd_and_grads(f, q, k, v):
    """Output and all three gradients of sum(f(q, k, v) * w) for a fixed
    random cotangent w."""
    w = jnp.asarray(onp.random.RandomState(7).randn(*q.shape)
                    .astype("float32"))
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + tuple(vjp(w.astype(out.dtype)))


def _assert_close(got, want, tol):
    for g, r in zip(got, want):
        scale = float(jnp.abs(r).max())
        err = float(jnp.abs(g.astype(jnp.float32) - r).max())
        assert err <= tol * scale, (err, scale)


class TestFlashTiles:
    """The three kernels at their own default tile shapes and the causal
    chunking at its edges (ISSUE 27), in interpret mode."""

    @pytest.fixture
    def scaled(self, monkeypatch):
        """The kernels' defaults at a cell's sequence length, everything
        (sequence, blocks, chunk rows) divided by ``by``."""
        def make(cell_seq, by):
            monkeypatch.setattr(FA, "_CHUNK", FA._CHUNK // by)
            blocks = tuple((bq // by, bk // by) for bq, bk in
                           FA._default_blocks(cell_seq, cell_seq))
            return cell_seq // by, blocks
        return make

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("d", [128, 64])
    @pytest.mark.parametrize("cell_seq", [2048, 16384])
    def test_default_tiles_scaled_down(self, scaled, cell_seq, d, dtype,
                                       causal):
        s, blocks = scaled(cell_seq, 8)
        q, k, v = (x.astype(dtype) for x in _qkv(b=1, h=1, s=s, d=d))
        got = _fwd_and_grads(
            lambda a, b, c: FA._flash(a, b, c, causal, d ** -0.5, blocks,
                                      True), q, k, v)
        want = _fwd_and_grads(
            lambda a, b, c: attention_reference(a, b, c, causal=causal),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        assert all(g.dtype == q.dtype for g in got)
        _assert_close(got, want, 4e-2 if dtype == "bfloat16" else 2e-5)

    @pytest.mark.parametrize("blocks,chunk", [
        ((256, 256), 128),      # two chunks a diagonal tile, ranges end
        #                         on lane tiles: the shape of the chip's
        ((256, 256), 256),      # chunk equal to the block
        ((512, 512), 128),      # a sequence of exactly one block
        ((512, 128), 128),      # tall tile: four diagonal bodies
        ((128, 512), 64),       # wide tile, chunks inside one lane tile
        ((64, 128), 8),         # too many diagonal bodies: dynamic rel
    ])
    def test_causal_chunks(self, monkeypatch, blocks, chunk):
        monkeypatch.setattr(FA, "_CHUNK", chunk)
        q, k, v = _qkv(b=1, h=2, s=512, d=64)
        got = _fwd_and_grads(
            lambda a, b, c: flash_attention(a, b, c, causal=True,
                                            block_q=blocks[0],
                                            block_k=blocks[1],
                                            interpret=True), q, k, v)
        want = _fwd_and_grads(
            lambda a, b, c: attention_reference(a, b, c, causal=True),
            q, k, v)
        _assert_close(got, want, 2e-5)

    def test_cross_attention_per_kernel_tiles(self):
        """sq != sk, non-causal, each kernel on a tile shape of its own."""
        q, _, _ = _qkv(b=1, h=2, s=256, d=64)
        _, k, v = _qkv(b=1, h=2, s=512, d=64, seed=1)
        blocks = ((128, 256), (256, 128), (128, 512))
        got = _fwd_and_grads(
            lambda a, b, c: FA._flash(a, b, c, False, 0.125, blocks, True),
            q, k, v)
        want = _fwd_and_grads(attention_reference, q, k, v)
        _assert_close(got, want, 2e-5)

    @pytest.mark.parametrize("seq,at_most", [(2048, 1.25), (16384, 1.06)])
    def test_flash_entry_reports_tiles_and_computed_pairs(self, seq,
                                                          at_most):
        """metrics()['flash'] has one entry a call shape: each kernel's
        tile and the score pairs it computes over the pairs the mask
        keeps. Whole diagonal tiles cost 1.50x at 2048 and 1.125x at
        16384 with 1024 x 1024 tiles; chunked ones stay under the
        issue's 1.25x / 1.06x."""
        import mxnet_tpu as mx
        x = jax.ShapeDtypeStruct((1, 2, seq, 128), jnp.bfloat16)
        jax.eval_shape(lambda a, b, c: flash_attention(
            a, b, c, causal=True, interpret=True), x, x, x)
        entry = mx.profiler.metrics()["flash"][
            "1x2x%dx%dx128.bfloat16.causal" % (seq, seq)]
        kernels = dict(part.split("=") for part in entry.split())
        assert sorted(kernels) == ["dkv", "dq", "fwd"]
        for name, val in kernels.items():
            tile, ratio = val.split("/")
            bq, bk = map(int, tile.split("x"))
            assert seq % bq == 0 and seq % bk == 0
            assert 1.0 <= float(ratio) <= at_most, (name, val)
            whole = FA._computed_pairs(seq, seq, bq, bk, bk if name == "dkv"
                                       else bq, True, name == "dkv")
            assert whole / (seq * (seq + 1) // 2) > float(ratio)
        assert any(line.startswith("flash: ")
                   for line in mx.profiler.dumps().splitlines())


class TestCompression:
    def test_semantics_match_reference_struct(self):
        """ref: gradient_compression-inl.h quantize_2bit — +thr / -thr / 0
        with error feedback."""
        grad = jnp.array([0.6, -0.7, 0.1, 0.0, 0.49, -0.5])
        res = jnp.zeros(6)
        words, new_res = quantize_2bit_jnp(grad, res, 0.5)
        deq = dequantize_2bit_jnp(words, 6, 0.5)
        onp.testing.assert_allclose(
            onp.asarray(deq), [0.5, -0.5, 0.0, 0.0, 0.0, -0.5], atol=1e-6)
        # residual keeps what quantization dropped
        onp.testing.assert_allclose(
            onp.asarray(new_res),
            [0.1, -0.2, 0.1, 0.0, 0.49, 0.0], atol=1e-6)

    def test_error_feedback_identity(self):
        rng = onp.random.RandomState(0)
        grad = jnp.array(rng.randn(1000).astype("float32"))
        words, new_res = quantize_2bit_jnp(grad, jnp.zeros(1000), 0.5)
        deq = dequantize_2bit_jnp(words, 1000, 0.5)
        # deq + residual == grad exactly (nothing lost, only deferred)
        assert float(jnp.abs((deq + new_res) - grad).max()) < 1e-6

    def test_pallas_matches_jnp(self):
        rng = onp.random.RandomState(1)
        grad = jnp.array(rng.randn(4096).astype("float32"))
        res = jnp.array(rng.randn(4096).astype("float32")) * 0.1
        w_j, r_j = quantize_2bit_jnp(grad, res, 0.5)
        w_p, r_p = quantize_2bit(grad, res, 0.5, interpret=True)
        assert bool((w_j == w_p).all())
        assert float(jnp.abs(r_j - r_p).max()) == 0.0
        d_j = dequantize_2bit_jnp(w_j, 4096, 0.5)
        d_p = dequantize_2bit(w_p, 4096, 0.5, interpret=True)
        assert bool((d_j == d_p).all())

    def test_ragged_length(self):
        grad = jnp.ones((37,)) * 0.6
        words, res = quantize_2bit_jnp(grad, jnp.zeros(37), 0.5)
        assert words.shape == (3,)  # ceil(37/16)
        deq = dequantize_2bit_jnp(words, 37, 0.5)
        assert deq.shape == (37,)
        assert bool((deq == 0.5).all())

    def test_compression_ratio(self):
        grad = jnp.zeros((1600,), jnp.float32)
        words, _ = quantize_2bit_jnp(grad, jnp.zeros(1600), 0.5)
        assert grad.nbytes / words.nbytes == 16.0


class TestKVStoreCompression:
    def test_kvstore_roundtrip_with_residual(self):
        import mxnet_tpu as mx
        kv = mx.kv.create("local")
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5,
                                     "size_lower_bound": 0})
        kv.init(3, mx.nd.zeros((8, 8)))
        g = mx.nd.ones((8, 8)) * 0.3  # below threshold -> all zeros, kept
        kv.push(3, g)
        out = mx.nd.zeros((8, 8))
        kv.pull(3, out=out)
        assert onp.abs(out.asnumpy()).max() == 0.0  # quantized to zero
        kv.push(3, g)  # residual 0.3 + 0.3 = 0.6 >= thr -> fires now
        kv.pull(3, out=out)
        assert onp.allclose(out.asnumpy(), 0.5)

    def test_small_tensors_not_compressed(self):
        import mxnet_tpu as mx
        kv = mx.kv.create("local")
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        kv.init(4, mx.nd.zeros((10,)))
        g = mx.nd.ones((10,)) * 0.01  # small bias-like gradient
        kv.push(4, g)
        out = mx.nd.zeros((10,))
        kv.pull(4, out=out)
        # below size_lower_bound: passes through uncompressed
        assert onp.allclose(out.asnumpy(), 0.01)


def test_flash_causal_rejects_unequal_lengths():
    """The fully-masked-row invariant is enforced at the public boundary
    (ADVICE r4): causal with kv shorter than q would leave leading rows
    with no visible keys and NaN silently in the kernel."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_kernels.flash_attention import flash_attention
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 128, 64), jnp.float32)
    with pytest.raises(ValueError, match="equal q/kv lengths"):
        flash_attention(q, kv, kv, causal=True)
    # non-causal cross-attention with unequal lengths stays legal
    out = flash_attention(q, kv, kv, causal=False, interpret=True)
    assert out.shape == q.shape

"""Bucketed, backward-overlapped gradient reduction
(parallel/overlap.py + the mesh-mode fused step + the chunked-CE
local-accumulation fix, ISSUE 7 tentpole b).

SCALING_r05: 256-chip efficiency is 84.5% with zero comm/compute
overlap and ~100% once the grad reduction hides under backward. These
tests pin the machinery that makes the overlap real: bucket planning,
the custom-vjp markers that place one collective per bucket
mid-backward, numerical parity with the unbucketed reduction, the
fused/parallel train steps that wire it in, and the chunked-CE
wire-bytes fix (unembedding grad accumulated locally, reduced once).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax.random as jr
from jax import lax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.parallel import (ShardedTrainStep, bucket_plan,
                                bucketed_reduce, create_mesh,
                                data_parallel, default_bucket_bytes, fsdp,
                                shard_map, tag_gradient_buckets)
from mxnet_tpu.parallel import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from comm_model import hlo_collective_bytes  # noqa: E402


def _leaves(*shapes, dtype=jnp.float32):
    return [jnp.zeros(s, dtype) for s in shapes]


class TestBucketPlan:
    def test_size_cap_splits(self):
        # 3 x 1KiB leaves under a 2KiB cap -> [0,1] then [2]
        leaves = _leaves((256,), (256,), (256,))
        plan = bucket_plan(leaves, bucket_bytes=2048)
        assert plan == [[0, 1], [2]]

    def test_dtype_homogeneous(self):
        leaves = [jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.bfloat16),
                  jnp.zeros(4, jnp.float32)]
        plan = bucket_plan(leaves, bucket_bytes=1 << 20)
        # one flat wire message per bucket => no dtype mixing
        for bucket in plan:
            dts = {leaves[i].dtype for i in bucket}
            assert len(dts) == 1
        assert [i for b in plan for i in b] == [0, 1, 2]  # order kept

    def test_oversize_leaf_gets_own_bucket(self):
        leaves = _leaves((16,), (4096,), (16,))
        plan = bucket_plan(leaves, bucket_bytes=256)
        assert [len(b) for b in plan] == [1, 1, 1]

    def test_env_default_cap(self, monkeypatch):
        monkeypatch.setenv("MXTPU_ELASTIC_BUCKET_MB", "2")
        assert default_bucket_bytes() == 2 << 20


@pytest.fixture()
def dp_mesh():
    return create_mesh(devices=jax.devices()[:4])  # dp=4


def _rand_leaves(key, shapes):
    ks = jr.split(key, len(shapes))
    return [jr.normal(k, s, jnp.float32) for k, s in zip(ks, shapes)]


class TestBucketedParity:
    SHAPES = [(8, 4), (32,), (4, 4, 2), (128,), (3,)]

    def test_bucketed_reduce_bitwise_equals_per_leaf_psum(self, dp_mesh):
        """Concatenation batches wire messages but never mixes leaves:
        each leaf's reduced value is bitwise what lax.psum gives."""
        leaves = _rand_leaves(jr.PRNGKey(0), self.SHAPES)

        def plain(*ls):
            return tuple(lax.psum(l, "dp") for l in ls)

        def bucketed(*ls):
            return tuple(bucketed_reduce(list(ls), "dp",
                                         bucket_bytes=256))

        specs = tuple(P() for _ in leaves)
        want = shard_map(plain, dp_mesh, in_specs=specs,
                         out_specs=specs, check_vma=False)(*leaves)
        got = shard_map(bucketed, dp_mesh, in_specs=specs,
                        out_specs=specs, check_vma=False)(*leaves)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))

    def test_tagged_backward_grads_bitwise_equal_unbucketed(self, dp_mesh):
        """Gradients through the bucket markers == psum of the plain
        gradients, bitwise — the markers change WHERE the collective
        sits in the backward, never what it computes."""
        ws = _rand_leaves(jr.PRNGKey(1), self.SHAPES)
        xs = _rand_leaves(jr.PRNGKey(2), self.SHAPES)

        def loss(ws_, xs_):
            return sum(jnp.sum(w * x) ** 2 for w, x in zip(ws_, xs_))

        def ref(ws_, xs_):
            g = jax.grad(loss)(list(ws_), list(xs_))
            return tuple(lax.psum(gi, "dp") for gi in g)

        def tagged(ws_, xs_):
            def loss_tagged(raw):
                return loss(tag_gradient_buckets(raw, "dp",
                                                 bucket_bytes=256), xs_)
            return tuple(jax.grad(loss_tagged)(list(ws_)))

        specs = tuple(P() for _ in ws)
        want = shard_map(ref, dp_mesh, in_specs=(specs, specs),
                         out_specs=specs, check_vma=False)(
            tuple(ws), tuple(xs))
        got = shard_map(tagged, dp_mesh, in_specs=(specs, specs),
                        out_specs=specs, check_vma=False)(
            tuple(ws), tuple(xs))
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))

    def test_bucketing_collapses_collective_count(self, dp_mesh):
        """The lowering carries ONE all-reduce per bucket, not one per
        leaf — the wire-batching half of the overlap story — and the
        compiled payload bytes match the unbucketed program exactly.
        The per-bucket count is read off the UN-optimised lowering:
        what the program asks for, whatever a backend's own all-reduce
        combiner merges afterwards (this XLA's CPU combiner folds the
        six per-leaf reductions into one by itself)."""
        shapes = [(64,)] * 6
        ws = _rand_leaves(jr.PRNGKey(3), shapes)
        xs = _rand_leaves(jr.PRNGKey(4), shapes)
        specs = tuple(P() for _ in ws)

        def loss(ws_, xs_):
            return sum(jnp.sum(w * x) ** 2 for w, x in zip(ws_, xs_))

        def lowered(fn):
            body = shard_map(fn, dp_mesh, in_specs=(specs, specs),
                             out_specs=specs, check_vma=False)
            return jax.jit(body).lower(tuple(ws), tuple(xs))

        def asked(fn):
            return lowered(fn).as_text(dialect="hlo").count(" all-reduce(")

        def ref(ws_, xs_):
            g = jax.grad(loss)(list(ws_), list(xs_))
            return tuple(lax.psum(gi, "dp") for gi in g)

        def tagged(ws_, xs_):
            def loss_tagged(raw):
                # 3 leaves x 256B per 768B bucket -> 2 buckets of 3
                return loss(tag_gradient_buckets(raw, "dp",
                                                 bucket_bytes=768), xs_)
            return tuple(jax.grad(loss_tagged)(list(ws_)))

        assert asked(ref) == 6
        assert asked(tagged) == 2
        b_ref, c_ref, _ = hlo_collective_bytes(
            lowered(ref).compile().as_text())
        b_tag, c_tag, _ = hlo_collective_bytes(
            lowered(tagged).compile().as_text())
        assert 1 <= c_tag["all-reduce"] <= c_ref["all-reduce"]
        assert b_tag["all-reduce"] == b_ref["all-reduce"]


def _dense_pair(seed=0):
    """Two structurally identical nets with identical init."""
    from mxnet_tpu.gluon import nn
    rs = np.random.RandomState(seed)
    w1 = rs.randn(16, 12).astype(np.float32) * 0.1
    b1 = np.zeros(16, np.float32)
    w2 = rs.randn(4, 16).astype(np.float32) * 0.1
    b2 = np.zeros(4, np.float32)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=12))
        net.add(nn.Dense(4, in_units=16))
        net.initialize()
        net.hybridize()
        params = [p for _, p in sorted(net.collect_params().items())]
        for p, v in zip(params, [b1, w1, b2, w2]
                        if params[0].shape == (16,) else [w1, b1, w2, b2]):
            if p.shape != v.shape:
                raise AssertionError("param order drifted")
            p.set_data(mx.nd.array(v))
        return net
    return build(), build()


class TestFusedStepMesh:
    def _train(self, net, mesh, steps=6):
        from mxnet_tpu import gluon
        loss_fn = gluon.loss.L2Loss()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        step = tr.fuse_step(lambda xx, yy: loss_fn(net(xx), yy),
                            mesh=mesh, bucket_bytes=512)
        rs = np.random.RandomState(7)
        losses = []
        for i in range(steps):
            x = mx.nd.array(rs.rand(8, 12).astype(np.float32))
            y = mx.nd.array(rs.rand(8, 4).astype(np.float32))
            losses.append(float(step(x, y, batch_size=8)
                                .asnumpy().mean()))
        params = [p.data().asnumpy()
                  for _, p in sorted(net.collect_params().items())]
        return losses, params

    def test_mesh_step_matches_single_device(self):
        """The mesh-sharded fused step (bucketed psum over 'dp') trains
        to the same trajectory as the plain single-device fused step —
        the overlap machinery must not change the math."""
        from mxnet_tpu.gluon import fused_step as fs
        net_a, net_b = _dense_pair()
        mesh = create_mesh(devices=jax.devices()[:4])
        losses_m, params_m = self._train(net_a, mesh)
        losses_p, params_p = self._train(net_b, None)
        np.testing.assert_allclose(losses_m, losses_p,
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(params_m, params_p):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        st = fs.stats()
        assert st["hits"] >= 1                   # mesh path compiled+hit

    def test_mesh_step_indivisible_batch_falls_back(self):
        """A batch 'dp' cannot split runs the eager path (counted),
        never a crash — and training continues."""
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon import fused_step as fs
        net, _ = _dense_pair(seed=1)
        mesh = create_mesh(devices=jax.devices()[:4])
        loss_fn = gluon.loss.L2Loss()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05})
        step = tr.fuse_step(lambda xx, yy: loss_fn(net(xx), yy),
                            mesh=mesh)
        rs = np.random.RandomState(3)
        before = fs.stats()["fallbacks"]
        x = mx.nd.array(rs.rand(7, 12).astype(np.float32))   # 7 % 4 != 0
        y = mx.nd.array(rs.rand(7, 4).astype(np.float32))
        out = step(x, y, batch_size=7)
        assert np.isfinite(out.asnumpy()).all()
        assert fs.stats()["fallbacks"] == before + 1
        # divisible batches still take the fused mesh path afterwards
        x8 = mx.nd.array(rs.rand(8, 12).astype(np.float32))
        y8 = mx.nd.array(rs.rand(8, 4).astype(np.float32))
        for _ in range(3):
            out = step(x8, y8, batch_size=8)
        assert np.isfinite(out.asnumpy()).all()


class TestShardedTrainStepOverlap:
    def _step(self, overlap):
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import mxnet_tpu.optimizer as opt
        rs = np.random.RandomState(11)
        net = nn.Dense(6, in_units=10)
        net.initialize()
        for _, p in sorted(net.collect_params().items()):
            p.set_data(mx.nd.array(
                rs.randn(*p.shape).astype(np.float32) * 0.1))
        mesh = create_mesh(devices=jax.devices()[:8])
        return ShardedTrainStep(net, L2Loss(),
                                opt.create("sgd", learning_rate=0.05,
                                           momentum=0.9),
                                strategy=data_parallel(mesh),
                                overlap_grads=overlap, bucket_bytes=128)

    def test_overlap_matches_gspmd_path(self):
        rs = np.random.RandomState(5)
        x = rs.rand(16, 10).astype(np.float32)
        y = rs.rand(16, 6).astype(np.float32)
        s_ref, s_ovl = self._step(False), self._step(True)
        for i in range(5):
            l_ref = s_ref(x, y)
            l_ovl = s_ovl(x, y)
            np.testing.assert_allclose(float(l_ref), float(l_ovl),
                                       rtol=1e-5, atol=1e-6)
        for k in s_ref.params:
            np.testing.assert_allclose(
                np.asarray(s_ref.params[k]), np.asarray(s_ovl.params[k]),
                rtol=1e-5, atol=1e-6, err_msg=k)

    def test_overlap_requires_pure_dp(self):
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.gluon.loss import L2Loss
        import mxnet_tpu.optimizer as opt
        net = nn.Dense(8, in_units=64)
        net.initialize()
        mesh = create_mesh(dp=2, fsdp=4)
        with pytest.raises(ValueError, match="pure data-parallel"):
            ShardedTrainStep(net, L2Loss(),
                             opt.create("sgd", learning_rate=0.01),
                             strategy=fsdp(mesh, min_size=64),
                             overlap_grads=True)


class TestChunkedCELocalAccum:
    def _cfg(self, **kw):
        base = dict(vocab_size=64, dim=16, n_layers=2, n_heads=4,
                    ffn_hidden=32, loss_chunks=4)
        base.update(kw)
        return T.TransformerConfig(**base)

    @pytest.mark.parametrize("axes", [{}, dict(tp=2)])
    def test_local_accum_matches_plain_chunked(self, axes):
        """ce_local_accum moves WHERE the unembedding-grad reduction
        happens (once, at the shard_map boundary) — loss and every
        gradient stay numerically identical; the tp variant also pins
        the distributed logsumexp + target gather."""
        cfg_a = self._cfg()
        cfg_b = self._cfg(ce_local_accum=True)
        # 4 devices: dp=4, or dp=2 x tp=2
        mesh = create_mesh(devices=jax.devices()[:4], **axes)
        params = T.init_params(jr.PRNGKey(0), cfg_a)
        toks = jr.randint(jr.PRNGKey(1), (4, 16), 0, 64)
        tgts = jr.randint(jr.PRNGKey(2), (4, 16), 0, 64)
        with mesh.mesh:
            la, ga = jax.value_and_grad(
                lambda p: T.loss_fn(p, toks, tgts, cfg_a, mesh))(params)
            lb, gb = jax.value_and_grad(
                lambda p: T.loss_fn(p, toks, tgts, cfg_b, mesh))(params)
        assert abs(float(la) - float(lb)) < 1e-5
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            ga, gb)

    def test_local_accum_cuts_wire_bytes(self):
        """The SCALING_r05 finding, fixed and measured: with the chunk
        scan inside shard_map the unembedding grad is reduced ONCE, so
        the pure-dp train step's all-reduce payload drops by
        ~(loss_chunks-1) * vocab * dim * 4 bytes."""
        V, D, chunks = 64, 16, 4
        bytes_by_cfg = {}
        for local in (False, True):
            cfg = self._cfg(ce_local_accum=local)
            mesh = create_mesh(dp=8)
            init_fn, step_fn = T.make_train_step(cfg, mesh)
            with mesh.mesh:
                state = init_fn(jr.PRNGKey(0))
                toks = jnp.zeros((8, 16), jnp.int32)
                txt = step_fn.lower(state, toks,
                                    toks).compile().as_text()
            by_kind, _, _ = hlo_collective_bytes(txt)
            bytes_by_cfg[local] = by_kind.get("all-reduce", 0)
        saved = bytes_by_cfg[False] - bytes_by_cfg[True]
        expect = (chunks - 1) * V * D * 4
        assert saved > 0, bytes_by_cfg
        # the win is the per-chunk re-reduction, within 25% (other
        # partitioner noise moves a few small ops between kinds)
        assert abs(saved - expect) <= 0.25 * expect, \
            (saved, expect, bytes_by_cfg)

    def test_bad_chunk_split_raises(self):
        cfg = self._cfg(ce_local_accum=True, loss_chunks=3)
        mesh = create_mesh(devices=jax.devices()[:4], sp=2)
        params = T.init_params(jr.PRNGKey(0), cfg)
        toks = jr.randint(jr.PRNGKey(1), (4, 16), 0, 64)
        with mesh.mesh, pytest.raises(ValueError,
                                      match="does not divide"):
            T.loss_fn(params, toks, toks, cfg, mesh)

"""Parallelism tests on the 8-device virtual CPU mesh.

Blueprint per SURVEY.md §4 "distributed tests without a real cluster": the
reference runs dist kvstore tests as local processes
(ci/docker/runtime_functions.sh:1281); here the mesh itself is the cluster
and shardings are validated by exact-numerics comparison against the
unsharded computation — the same check_consistency idea
(python/mxnet/test_utils.py:1314) across parallelism modes instead of
devices.
"""
import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import (create_mesh, data_parallel, fsdp,
                                tensor_parallel, ring_self_attention,
                                ulysses_attention, ShardedTrainStep,
                                functional_call, extract_params)
from mxnet_tpu.parallel.ring_attention import blockwise_attention
from mxnet_tpu.parallel import transformer as T


def _dense_attention(q, k, v, causal=False):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.fixture(scope="module")
def qkv():
    key = jr.PRNGKey(0)
    ks = jr.split(key, 3)
    shape = (2, 4, 32, 8)  # [B, H, S, D]
    return tuple(jr.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(qkv, causal):
    q, k, v = qkv
    mesh = create_mesh(dp=2, tp=2, sp=2)
    want = _dense_attention(q, k, v, causal)
    with mesh.mesh:
        got = ring_self_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(qkv, causal):
    q, k, v = qkv
    mesh = create_mesh(dp=2, tp=2, sp=2)
    want = _dense_attention(q, k, v, causal)
    with mesh.mesh:
        got = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_dense(qkv, causal):
    q, k, v = qkv
    want = _dense_attention(q, k, v, causal)
    got = blockwise_attention(q, k, v, block_size=8, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def _tiny_cfg(**kw):
    base = dict(vocab_size=64, dim=16, n_layers=2, n_heads=4, ffn_hidden=32)
    base.update(kw)
    return T.TransformerConfig(**base)


def test_transformer_ring_matches_local():
    """Same params, sharded-ring vs single-device local attention."""
    key = jr.PRNGKey(3)
    toks = jr.randint(jr.PRNGKey(4), (4, 16), 0, 64)
    cfg_local = _tiny_cfg(attn_mode="local")
    params = T.init_params(key, cfg_local)
    want = T.apply(params, toks, cfg_local)

    mesh = create_mesh(dp=2, tp=2, sp=2)
    cfg_ring = _tiny_cfg(attn_mode="ring")
    with mesh.mesh:
        got = T.apply(params, toks, cfg_ring, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-5)


def test_pipeline_matches_gspmd():
    """Explicit pp=2 pipeline produces the same loss as the pp=1 path."""
    key = jr.PRNGKey(5)
    toks = jr.randint(jr.PRNGKey(6), (4, 16), 0, 64)
    tgts = jr.randint(jr.PRNGKey(7), (4, 16), 0, 64)

    cfg1 = _tiny_cfg(attn_mode="local")
    params1 = T.init_params(key, cfg1)
    want = T.loss_fn(params1, toks, tgts, cfg1)

    cfg2 = _tiny_cfg(pp=2, n_microbatch=2)
    mesh = create_mesh(pp=2, dp=2, sp=2)
    params2 = T.init_params(key, cfg2)  # same weights, stacked [pp, L/pp]
    init_fn, step_fn = T.make_train_step(cfg2, mesh)
    with mesh.mesh:
        from mxnet_tpu.parallel import shard_map
        from jax.sharding import PartitionSpec as P
        specs = T.param_specs(cfg2)
        loss = shard_map(
            lambda ps, tk, tg: T._pipeline_loss_local(cfg2, ps, tk, tg),
            mesh=mesh.mesh,
            in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
            out_specs=P(), check_vma=False)(params2, toks, tgts)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-4)


def test_transformer_train_step_decreases_loss():
    mesh = create_mesh(dp=2, tp=2, sp=2)
    cfg = _tiny_cfg(attn_mode="ring")
    init_fn, step_fn = T.make_train_step(cfg, mesh, learning_rate=0.1)
    toks = jr.randint(jr.PRNGKey(8), (4, 16), 0, 64)
    tgts = jr.randint(jr.PRNGKey(9), (4, 16), 0, 64)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        state, loss0 = step_fn(state, toks, tgts)  # donates state buffers
        for _ in range(5):
            state, loss = step_fn(state, toks, tgts)
    assert float(loss) < float(loss0)


def test_moe_train_step_runs():
    mesh = create_mesh(dp=2, ep=2, tp=2)
    cfg = _tiny_cfg(num_experts=4, attn_mode="local")
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    toks = jr.randint(jr.PRNGKey(8), (4, 16), 0, 64)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        state, loss = step_fn(state, toks, toks)
    assert np.isfinite(float(loss))


def test_sharded_train_step_gluon_dp():
    """Gluon net + mxnet optimizer through one pjit'd DP step."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    import mxnet_tpu.optimizer as opt

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=20))
    net.add(nn.Dense(10, in_units=32))
    net.initialize()

    mesh = create_mesh(dp=8)
    step = ShardedTrainStep(net, SoftmaxCrossEntropyLoss(),
                            opt.create("sgd", learning_rate=0.1,
                                       momentum=0.9),
                            strategy=data_parallel(mesh))
    x = np.random.rand(16, 20).astype("float32")
    y = np.random.randint(0, 10, (16,)).astype("float32")
    losses = [step(x, y) for _ in range(8)]
    assert losses[-1] < losses[0]
    step.sync_to_block()  # params flow back into the Block


def test_sharded_train_step_fsdp():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    import mxnet_tpu.optimizer as opt

    net = nn.Dense(8, in_units=64)
    net.initialize()
    mesh = create_mesh(dp=2, fsdp=4)
    strat = fsdp(mesh, min_size=64)
    step = ShardedTrainStep(net, L2Loss(), opt.create("adam",
                                                      learning_rate=0.01),
                            strategy=strat)
    x = np.random.rand(8, 64).astype("float32")
    y = np.random.rand(8, 8).astype("float32")
    l0 = step(x, y)
    for _ in range(5):
        l1 = step(x, y)
    assert l1 < l0
    # weight (8, 64): fsdp axis must actually shard dim 1
    sh = step.params["weight"].sharding.spec
    assert "fsdp" in str(sh)


def test_functional_call_matches_eager():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=3)
    net.initialize()
    x = mx.nd.array(np.random.rand(2, 3).astype("float32"))
    want = net(x).asnumpy()
    params = extract_params(net)
    got = functional_call(net, params, [x])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_collectives_shard_map():
    from mxnet_tpu.parallel import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import all_reduce, reduce_scatter, ring_exchange
    mesh = create_mesh(dp=8)
    x = jnp.arange(16.0).reshape(8, 2)

    def body(x):
        return all_reduce(x, "dp")

    with mesh.mesh:
        got = shard_map(body, mesh=mesh.mesh, in_specs=P("dp"),
                        out_specs=P("dp"), check_vma=False)(x)
    want = np.tile(x.sum(0, keepdims=True), (8, 1))
    np.testing.assert_allclose(np.asarray(got), want)


def test_pipeline_embed_grad_synced_across_stages():
    """Regression: replicated embed/w_out grads must psum over 'pp' — only
    one stage touches them, others contribute zero."""
    mesh = create_mesh(pp=2, dp=2, sp=2)
    cfg = _tiny_cfg(pp=2, n_microbatch=2)
    init_fn, step_fn = T.make_train_step(cfg, mesh, learning_rate=0.1)
    toks = jr.randint(jr.PRNGKey(0), (4, 16), 0, 64)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(1))
        state, _ = step_fn(state, toks, toks)
    embed = state[0]["embed"]
    shards = [np.asarray(s.data) for s in embed.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_allclose(s, shards[0], rtol=1e-6, atol=1e-7)


def test_moe_aux_loss_in_objective():
    """Regression: load-balance aux loss must reach the training loss."""
    cfg = _tiny_cfg(num_experts=4, attn_mode="local")
    params = T.init_params(jr.PRNGKey(0), cfg)
    toks = jr.randint(jr.PRNGKey(1), (2, 8), 0, 64)
    l_with = float(T.loss_fn(params, toks, toks, cfg, aux_weight=1.0))
    l_without = float(T.loss_fn(params, toks, toks, cfg, aux_weight=0.0))
    assert l_with != l_without


def test_fsdp_accepts_raw_mesh():
    from jax.sharding import PartitionSpec as P
    mesh = create_mesh(dp=2, fsdp=4)
    strat = fsdp(mesh.mesh, min_size=16)  # raw jax Mesh, not DeviceMesh
    spec = strat.param_rules.spec_for("weight", (8, 64))
    assert spec == P(None, "fsdp")


@pytest.mark.slow
def test_realistic_shapes_dp_tp_sp_train_step():
    """Non-trivial block sizes (dim 256, seq 512) on the 8-device CPU
    mesh — sharding arithmetic errors that only trigger past the tiny
    dryrun shapes (VERDICT r1 weak #8) surface here, before real
    hardware. One full train step; loss must be finite."""
    cfg = T.TransformerConfig(vocab_size=512, dim=256, n_layers=2,
                              n_heads=8, ffn_hidden=512, max_seq_len=512,
                              attn_mode="ring")
    mesh = create_mesh(dp=2, tp=2, sp=2)
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        toks = jr.randint(jr.PRNGKey(1), (4, 512), 0, 512)
        state, loss = step_fn(state, toks, toks)
        assert np.isfinite(float(loss)), float(loss)


@pytest.mark.slow
def test_realistic_shapes_pipeline():
    """GPipe pp=2 at dim 256 / seq 512 on the CPU mesh."""
    cfg = T.TransformerConfig(vocab_size=512, dim=256, n_layers=4,
                              n_heads=8, ffn_hidden=512, max_seq_len=512,
                              pp=2, n_microbatch=2, attn_mode="local")
    mesh = create_mesh(pp=2, dp=2, sp=2)
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        toks = jr.randint(jr.PRNGKey(1), (4, 512), 0, 512)
        state, loss = step_fn(state, toks, toks)
        assert np.isfinite(float(loss)), float(loss)


class TestRingFlash:
    """ring x flash composition (parallel/ring_flash.py): per-hop Pallas
    blocks (interpret mode on CPU) vs dense full attention, forward and
    gradients."""

    def _data(self, B=1, H=2, S=64, D=32, seed=0):
        import numpy as onp
        rs = onp.random.RandomState(seed)
        mk = lambda s: jnp.asarray(rs.randn(B, H, S, D).astype("float32"))  # noqa: E731
        return mk(0), mk(1), mk(2)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        from mxnet_tpu.parallel.ring_flash import ring_flash_self_attention
        from mxnet_tpu.pallas_kernels.flash_attention import \
            attention_reference
        q, k, v = self._data()
        mesh = create_mesh(sp=4)
        got = ring_flash_self_attention(q, k, v, mesh, causal=causal,
                                        batch_axis=None, head_axis=None,
                                        interpret=True)
        want = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16_matches_dense(self):
        """f32 hop accumulator: bf16 ring output stays at the dense
        reference's rounding level even with 8 hops."""
        from mxnet_tpu.parallel.ring_flash import ring_flash_self_attention
        from mxnet_tpu.pallas_kernels.flash_attention import \
            attention_reference
        q, k, v = self._data(S=64)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        mesh = create_mesh(sp=8)
        got = ring_flash_self_attention(qb, kb, vb, mesh, causal=True,
                                        batch_axis=None, head_axis=None,
                                        interpret=True)
        want = attention_reference(q, k, v, causal=True)
        err = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max()
        assert err < 0.03, err

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_dense(self, causal):
        from mxnet_tpu.parallel.ring_flash import ring_flash_self_attention
        from mxnet_tpu.pallas_kernels.flash_attention import \
            attention_reference
        q, k, v = self._data()
        mesh = create_mesh(sp=4)

        def ring_loss(a, b, c):
            out = ring_flash_self_attention(a, b, c, mesh, causal=causal,
                                            batch_axis=None,
                                            head_axis=None,
                                            interpret=True)
            return (out.astype(jnp.float32) ** 2).sum()

        def dense_loss(a, b, c):
            return (attention_reference(
                a, b, c, causal=causal).astype(jnp.float32) ** 2).sum()

        g_ring = jax.grad(ring_loss, (0, 1, 2))(q, k, v)
        g_dense = jax.grad(dense_loss, (0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
                err_msg="d%s mismatch" % name)


def test_transformer_ring_flash_matches_local():
    """attn_mode='ring_flash' end-to-end in the transformer vs the
    unsharded local path."""
    key = jr.PRNGKey(3)
    toks = jr.randint(jr.PRNGKey(4), (4, 16), 0, 64)
    cfg_local = _tiny_cfg(attn_mode="local")
    params = T.init_params(key, cfg_local)
    want = T.apply(params, toks, cfg_local)
    mesh = create_mesh(dp=2, tp=2, sp=2)
    cfg_rf = _tiny_cfg(attn_mode="ring_flash")
    with mesh.mesh:
        got = T.apply(params, toks, cfg_rf, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_chunked_loss_matches_unchunked():
    """loss_chunks>1 must be numerically identical to the full-logits
    path (the [B,S,V] tensor never materializes; bench batch-8 enabler)."""
    cfg_a = _tiny_cfg()
    cfg_b = _tiny_cfg(loss_chunks=4)
    params = T.init_params(jr.PRNGKey(0), cfg_a)
    toks = jr.randint(jr.PRNGKey(1), (2, 16), 0, 64)
    tgts = jr.randint(jr.PRNGKey(2), (2, 16), 0, 64)
    la = T.loss_fn(params, toks, tgts, cfg_a)
    lb = T.loss_fn(params, toks, tgts, cfg_b)
    assert abs(float(la) - float(lb)) < 1e-5
    # gradients agree too
    ga = jax.grad(lambda p: T.loss_fn(p, toks, tgts, cfg_a))(params)
    gb = jax.grad(lambda p: T.loss_fn(p, toks, tgts, cfg_b))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                 np.asarray(b),
                                                 rtol=2e-4, atol=2e-5),
        ga, gb)


def test_selective_remat_matches_full():
    """remat_save=("ffn_prod",) changes memory planning, not numerics."""
    cfg_a = _tiny_cfg()
    cfg_b = _tiny_cfg(remat_save=("ffn_prod",))
    params = T.init_params(jr.PRNGKey(0), cfg_a)
    toks = jr.randint(jr.PRNGKey(3), (2, 16), 0, 64)
    tgts = jr.randint(jr.PRNGKey(4), (2, 16), 0, 64)
    ga = jax.grad(lambda p: T.loss_fn(p, toks, tgts, cfg_a))(params)
    gb = jax.grad(lambda p: T.loss_fn(p, toks, tgts, cfg_b))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                 np.asarray(b),
                                                 rtol=2e-4, atol=2e-5),
        ga, gb)


def test_flash_block_defaults_table():
    """Each kernel has a default tile shape of its own, a function of the
    call's shape and dtype, clamped to the sequence length."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_kernels.flash_attention import _default_blocks
    # (forward, dq, dk/dv) at the benchmark's two attention shapes
    assert _default_blocks(2048, 2048) == (
        (2048, 1024), (2048, 2048), (2048, 2048))
    assert _default_blocks(16384, 16384) == _default_blocks(2048, 2048)
    # 4-byte operands and heads wider than 128 halve the tall side
    assert _default_blocks(8192, 8192, 128, jnp.float32) == (
        (1024, 1024),) * 3
    assert _default_blocks(8192, 8192, 256) == ((1024, 1024),) * 3
    # cross-attention: q and kv blocks clamp to their own lengths
    assert _default_blocks(512, 4096) == ((512, 1024), (512, 2048),
                                          (512, 2048))
    for bq, bk in _default_blocks(64, 64):
        assert bq <= 64 and bk <= 64

"""The state-space mixer's parts (``parallel/ssm.py``): the chunked scan
against the recurrence it stands for, position by position; the conv as
shifted multiply-adds against its explicit sum, and ``conv_silu`` (shifts in
the activations' type, a backward of its own) against that; how the heads go
in blocks; what the mixer's gradient pads."""
import types

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.parallel import ssm

B, H, P, N, Q = 2, 6, 8, 16, 16


def _recurrence(xs, dt, a_head, bm, cm, d_head):
    """S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t; y_t = S_t C_t + D xs_t."""
    def step(state, at):
        x, d, b, c = at
        state = jnp.exp(d * a_head)[..., None, None] * state \
            + (d[..., None] * x)[..., None] * b[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c) \
            + d_head[:, None] * x
    _, y = jax.lax.scan(step, jnp.zeros((xs.shape[0], H, P, N)),
                        tuple(jnp.swapaxes(a, 0, 1)
                              for a in (xs, dt, bm, cm)))
    return jnp.swapaxes(y, 0, 1)


def _inputs(seq):
    k = jax.random.split(jax.random.PRNGKey(seq), 6)
    return (jax.random.normal(k[0], (B, seq, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, seq, H))),
            -jnp.exp(2.0 * jax.random.uniform(k[2], (H,))),
            jax.random.normal(k[3], (B, seq, N)),
            jax.random.normal(k[4], (B, seq, N)),
            1.0 + jax.random.uniform(k[5], (H,)))


def _scan_in_blocks(args, heads_at_once):
    """The scan with the heads in blocks of ``heads_at_once`` (None: the
    program's own choice, which is all ``chunked_scan`` offers)."""
    if heads_at_once is None:
        return ssm.chunked_scan(*args, Q)
    return ssm._scan(*args, *ssm._blocks(B, args[0].shape[1], H, Q,
                                         heads_at_once))


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_chunked_scan_is_the_recurrence(chunks):
    args = _inputs(chunks * Q)
    want = _recurrence(*args)
    for hb in (None, 2, 3):
        got = _scan_in_blocks(args, hb)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(
            jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_chunked_scans_gradients_are_the_recurrences(chunks):
    args = _inputs(chunks * Q)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(_scan_in_blocks(a, 2))),
                   argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(_recurrence(*a))),
                    argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * float(
            jnp.max(jnp.abs(w)))


def test_a_sequence_shorter_than_a_chunk_is_one_chunk():
    args = _inputs(Q // 2)
    assert float(jnp.max(jnp.abs(ssm.chunked_scan(*args, Q)
                                 - _recurrence(*args)))) < 1e-4


def test_a_head_block_that_does_not_divide_the_heads_is_refused_by_name():
    with pytest.raises(ValueError, match="heads_at_once=4 does not divide"):
        _scan_in_blocks(_inputs(Q), 4)
    with pytest.raises(ValueError, match="not whole chunks of 16"):
        ssm.chunked_scan(*_inputs(Q + Q // 2), Q)


def test_the_conv_is_its_explicit_sum_from_the_sequences_start():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 9, 5))
    w = jax.random.normal(k[1], (4, 5))
    b = jax.random.normal(k[2], (5,))
    got = onp.asarray(ssm.conv_taps(x, w, b))
    want = onp.zeros_like(got)
    for t in range(9):
        want[:, t] = onp.asarray(b)
        for tap in range(4):        # tap k on position t - 3 + k
            if t - 3 + tap >= 0:
                want[:, t] += onp.asarray(w[tap] * x[:, t - 3 + tap])
    assert onp.max(onp.abs(got - want)) < 1e-5
    # position 0 sees itself alone, through the LAST tap
    assert onp.max(onp.abs(got[:, 0] - onp.asarray(b + w[3] * x[:, 0]))) < 1e-6


def _conv_reference(x, w, b):
    """What ``conv_silu`` replaced in the mixer, and JAX's derivative of it."""
    return jax.nn.silu(ssm.conv_taps(x, w, b)).astype(x.dtype)


def _conv_inputs(dtype, batch, seq, stack=None, channels=5, taps=4):
    k = jax.random.split(jax.random.PRNGKey(seq), 4)
    lead = () if stack is None else (stack,)
    return (jax.random.normal(k[0], (batch, seq, channels)).astype(dtype),
            jax.random.normal(k[1], lead + (taps, channels)).astype(dtype),
            jax.random.normal(k[2], lead + (channels,)).astype(dtype),
            jax.random.normal(k[3], (batch, seq, channels)).astype(dtype))


def _under(how, conv):
    """``conv`` as the step runs it: as it is, under a remat, or as the body
    of a scan over stacked leaves (each layer's output the next one's x)."""
    if how == "checkpoint":
        return jax.checkpoint(conv)
    if how != "scan":
        return conv

    def scanned(x, w, b):
        return jax.lax.scan(lambda x, wb: (conv(x, *wb), None), x, (w, b))[0]
    return scanned


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("how,batch,seq", [
    ("two_sequences", 2, 9), ("shorter_than_the_taps", 1, 2),
    ("one_chunk", 2, Q), ("checkpoint", 2, 9), ("scan", 2, 9)])
def test_conv_silu_is_the_shifted_sum_and_its_derivative(how, batch, seq,
                                                         dtype):
    x, w, b, dy = _conv_inputs(dtype, batch, seq, 3 if how == "scan" else None)
    got_fn, want_fn = _under(how, ssm.conv_silu), _under(how, _conv_reference)
    got, back = jax.vjp(got_fn, x, w, b)
    want, want_back = jax.vjp(want_fn, x, w, b)
    # the forward to the bit: a shift and a cast commute
    assert got.dtype == x.dtype
    assert onp.array_equal(onp.asarray(got.astype(jnp.float32)),
                           onp.asarray(want.astype(jnp.float32)))
    # float32: the same derivative in another order; bfloat16: each side
    # rounds its float32 result once
    tol = 1e-5 if dtype == "float32" else 2 * 2.0 ** -8
    for name, g, r in zip("xwb", back(dy), want_back(dy)):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(g - r))) <= tol * float(
            jnp.max(jnp.abs(r))), name
    if batch == 2:
        # nothing passes from one sequence of a batch to the other
        other = x.at[1].set(-x[1])
        assert bool(jnp.all(got_fn(other, w, b)[0] == got[0]))
        dx = jax.grad(lambda x: jnp.sum(jnp.sin(
            got_fn(x, w, b)[0].astype(jnp.float32))))(x)
        assert float(jnp.max(jnp.abs(dx[0]))) > 0.0
        assert not bool(jnp.any(dx[1]))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_the_mixers_gradient_pads_nothing_in_float32():
    """Between its products the mixer's [tokens, channels] arrays stay in
    the activations' type: the shifts are pads of bfloat16 arrays, forward
    and backward, and the scan's output reaches the gate behind a barrier."""
    cfg = types.SimpleNamespace(dim=32, ssm_heads=4, ssm_head_size=8,
                                ssm_state=16, ssm_conv=4, ssm_chunk=Q,
                                norm_eps=1e-5)
    keys = jax.random.split(jax.random.PRNGKey(0), 9)
    lp = {n: jax.random.normal(k, shape).astype(jnp.bfloat16)
          for k, (n, (shape, _, _)) in zip(keys,
                                           ssm.mixer_leaves(cfg).items())}
    h = jax.random.normal(keys[8], (2, 2 * Q, 32)).astype(jnp.bfloat16)

    def loss(h, lp):
        out = jax.checkpoint(lambda h, lp: ssm.mixer(h, lp, cfg))(h, lp)
        return jnp.sum(out.astype(jnp.float32))

    eqns = list(_equations(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1)))(h, lp).jaxpr))
    pads = [e for e in eqns if e.primitive.name == "pad"]
    # x in the forward, x again and dy in the backward, once more each in
    # the remat's re-run of the forward
    assert len(pads) >= 3
    assert {str(e.outvars[0].aval.dtype) for e in pads} == {"bfloat16"}
    barriers = [e for e in eqns
                if e.primitive.name == "optimization_barrier"]
    assert barriers and all(
        v.aval.dtype == jnp.bfloat16 and v.aval.shape == (2, 2 * Q, 32)
        for e in barriers for v in e.outvars)


def test_the_least_bytes_of_conv_and_gate_at_the_published_widths():
    # 8192 tokens, 8448 conv channels, 8192 gated: five and eight passes
    assert ssm.stage_bytes(1, 8192, 8448, 8192, 2) \
        == 2 * 8192 * (5 * 8448 + 8 * 8192) == 1_765_801_984


def test_the_heads_go_in_blocks_chosen_from_shapes():
    # 8192 tokens in chunks of 256: a head's decays are 8 MiB, eight fit
    assert ssm.scan_temp_bytes(1, 8192, 256, 1) == 8 << 20
    assert ssm.block_heads(1, 8192, 256, 128) == 8
    assert ssm.scan_temp_bytes(1, 8192, 256, 8) == 64 << 20 < 0.3e9 / 4
    assert ssm.block_heads(2, 8192, 256, 128) == 4
    assert ssm.block_heads(2, 128, 32, 8) == 8          # all of them
    assert ssm.block_heads(64, 8192, 256, 6) == 1       # never none
    # all heads at once would be a gibibyte
    assert ssm.scan_temp_bytes(1, 8192, 256, 128) == 1 << 30


def test_the_leaves_start_where_the_configuration_says():
    a_log = ssm.init_leaf(jax.random.PRNGKey(1), "a_log", (4096,))
    assert 0.0 <= float(a_log.min()) and float(a_log.max()) <= onp.log(16.0)
    step = jax.nn.softplus(ssm.init_leaf(jax.random.PRNGKey(2), "dt_bias",
                                         (4096,)))
    assert 0.000999 <= float(step.min()) and float(step.max()) <= 0.1001

"""The state-space mixer's parts (``parallel/ssm.py``): the chunked scan
against the recurrence it stands for, position by position; the conv as
shifted multiply-adds against its explicit sum; how the heads go in
blocks."""
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

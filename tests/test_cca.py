"""Compressed convolutional attention's mixing stage (``parallel/cca.py``)
against explicit loops over positions, heads and taps in numpy: both
convolutions, the mean's grouping, the unit norms and the temperature, the
value shift at position 0; partial rotary positions against ``_rope`` at the
same base. Float32, tiny shapes."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.parallel import cca  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402

B, S, H, G, D = 2, 6, 4, 2, 8


@pytest.fixture(scope="module")
def stage():
    rng = onp.random.RandomState(7)
    n = lambda *shape: rng.standard_normal(shape).astype(onp.float32)  # noqa: E731
    lp = {"cca_conv0_w": n(2, (H + G) * D), "cca_conv0_b": n((H + G) * D),
          "cca_conv1_w": n(2, H + G, D, D), "cca_conv1_b": n(H + G, D),
          "cca_temp": 1.0 + 0.5 * n(G)}
    return lp, n(B, S, H, D), n(B, S, G, D)


def _loops(lp, q0, k0, conv0=True, conv1=True):
    """(q, k) before the norms, position by position and tap by tap."""
    c = onp.concatenate([q0, k0], axis=2)                  # [B, S, H + G, D]
    a = lp["cca_conv0_w"].reshape(2, H + G, D)
    b0 = lp["cca_conv0_b"].reshape(H + G, D)
    c1 = onp.zeros_like(c)
    for t in range(S):
        before = c[:, t - 1] if t else onp.zeros_like(c[:, 0])
        c1[:, t] = a[0] * before + a[1] * c[:, t] + b0
    c2 = onp.zeros_like(c)
    for t in range(S):
        for j in range(H + G):
            before = c1[:, t - 1, j] if t else onp.zeros_like(c1[:, 0, j])
            c2[:, t, j] = before @ lp["cca_conv1_w"][0, j] \
                + c1[:, t, j] @ lp["cca_conv1_w"][1, j] + lp["cca_conv1_b"][j]
    m_q = onp.zeros_like(q0)
    m_k = onp.zeros_like(k0)
    for i in range(H):
        m_q[:, :, i] = (q0[:, :, i] + k0[:, :, i // (H // G)]) / 2
    for g in range(G):
        m_k[:, :, g] = onp.mean(
            m_q[:, :, g * (H // G):(g + 1) * (H // G)], axis=2)
    return c2[:, :, :H] + m_q, c2[:, :, H:] + m_k


def test_the_stage_is_the_loops_over_positions_heads_and_taps(stage):
    lp, q0, k0 = stage
    q, k = cca.mix(jnp.asarray(q0), jnp.asarray(k0),
                   jax.tree_util.tree_map(jnp.asarray, lp))
    want_q, want_k = _loops(lp, q0, k0)
    unit = lambda x: x / onp.linalg.norm(x, axis=-1, keepdims=True) \
        * D ** 0.5                                          # noqa: E731
    onp.testing.assert_allclose(q, unit(want_q), rtol=2e-5, atol=2e-5)
    onp.testing.assert_allclose(
        k, unit(want_k) * lp["cca_temp"][:, None], rtol=2e-5, atol=2e-5)
    # unit length times sqrt(d), the temperature a key-value head on k only
    onp.testing.assert_allclose(onp.linalg.norm(q, axis=-1), D ** 0.5,
                                rtol=1e-5)
    onp.testing.assert_allclose(
        onp.linalg.norm(k, axis=-1),
        onp.broadcast_to(onp.abs(lp["cca_temp"]) * D ** 0.5, (B, S, G)),
        rtol=1e-5)


def test_position_zero_reads_nought_before_it(stage):
    """Both convolutions are causal: what position 0 gets does not depend
    on any other position, and what t gets not on any later one."""
    lp, q0, k0 = stage
    lp = jax.tree_util.tree_map(jnp.asarray, lp)
    q, k = cca.mix(jnp.asarray(q0), jnp.asarray(k0), lp)
    later = q0.copy()
    later[:, 3:] += 1.0
    q2, k2 = cca.mix(jnp.asarray(later), jnp.asarray(k0), lp)
    onp.testing.assert_array_equal(q[:, :3], q2[:, :3])
    onp.testing.assert_array_equal(k[:, :3], k2[:, :3])
    assert not onp.allclose(q[:, 3:], q2[:, 3:])
    # two taps each: position 3 reaches position 4 through conv 0 and 5
    # through both, and no further
    first = q0.copy()
    first[:, 0] += 1.0
    q3, _ = cca.mix(jnp.asarray(first), jnp.asarray(k0), lp)
    assert not onp.allclose(q[:, 2], q3[:, 2])
    onp.testing.assert_array_equal(q[:, 3:], q3[:, 3:])


def test_the_value_shift_moves_one_position_and_starts_from_nought():
    v = jnp.arange(B * S * 1 * D, dtype=jnp.float32).reshape(B, S, 1, D) + 1
    got = cca.shift(v)
    onp.testing.assert_array_equal(got[:, 0], 0)
    onp.testing.assert_array_equal(got[:, 1:], v[:, :-1])


def _cfg(**over):
    return T.TransformerConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=H, n_kv_heads=G,
        head_size=D, **over)


def test_partial_rotary_is_rope_on_the_first_dims_and_passes_the_rest():
    rng = onp.random.RandomState(3)
    a = jnp.asarray(rng.standard_normal((B, S, H, D)).astype(onp.float32))
    positions = jnp.arange(S)
    theta = 5000000.0
    got = T._rotated(_cfg(rope_theta=theta, rope_dims=D // 2), a, positions)
    turned = jnp.transpose(T._rope(jnp.transpose(a[..., :D // 2],
                                                 (0, 2, 1, 3)),
                                   positions, theta), (0, 2, 1, 3))
    onp.testing.assert_array_equal(got[..., :D // 2], turned)
    onp.testing.assert_array_equal(got[..., D // 2:], a[..., D // 2:])
    # pairs (j, j + rope_dims / 2), by hand at one position and head
    t, j, half = 4, 1, D // 4
    freq = theta ** (-j / half)
    x1, x2 = float(a[0, t, 0, j]), float(a[0, t, 0, j + half])
    assert float(got[0, t, 0, j]) == pytest.approx(
        x1 * onp.cos(t * freq) - x2 * onp.sin(t * freq), rel=1e-5)
    # the base is an argument whose default is the one there was
    whole = T._rotated(_cfg(), a, positions)
    plain = jnp.transpose(T._rope(jnp.transpose(a, (0, 2, 1, 3)), positions),
                          (0, 2, 1, 3))
    onp.testing.assert_array_equal(whole, plain)
    assert not onp.allclose(
        whole, T._rotated(_cfg(rope_theta=theta), a, positions))


def test_the_stages_leaves_are_rows_of_the_one_table():
    plain = T._layer_leaves(_cfg())
    mixed = T._layer_leaves(_cfg(qk_mix="cca", v_shift=True,
                                 residual_scaling=True))
    assert set(mixed) - set(plain) == {
        "cca_conv0_w", "cca_conv0_b", "cca_conv1_w", "cca_conv1_b",
        "cca_temp", "wv_cur", "wv_prev"} | {
        "res%s_%s" % (h, n) for h in "12" for n in "stuw"}
    assert set(plain) - set(mixed) == {"wv"}
    assert mixed["cca_conv0_w"][0] == (2, (H + G) * D)
    assert mixed["cca_conv1_w"][0] == (2, H + G, D, D)
    assert mixed["wv_cur"][0] == mixed["wv_prev"][0] == (32, 1, D)
    cfg = _cfg(qk_mix="cca", v_shift=True, residual_scaling=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)["layers"]
    # scales start at one, biases at nought
    for name, want in (("res1_s", 1), ("res2_u", 1), ("res1_t", 0),
                       ("res2_w", 0), ("cca_temp", 1), ("cca_conv0_b", 0)):
        onp.testing.assert_array_equal(params[name], want)
    assert dataclasses.replace(cfg, mix_taps=(3, 2)) != cfg


def test_mix_bytes_counts_five_passes_of_the_channels_and_four_of_the_shift():
    assert cca.mix_bytes(4, 8192, 1280, 128, 2) == \
        2 * 4 * 8192 * (5 * 1280 + 4 * 128)

"""The flash kernels with grouped-query heads and a sliding window, in
interpret mode at the smallest tiles, against ``attention_reference`` with
the same mask: forward and all three gradients. With ``window=None`` and
G = H the step tables, the bodies and every output bit are the parent's
(8ac7738: pinned below)."""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as onp
import pytest

FA = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")


def _qkvg(b, h, g, s, d=128, dtype=jnp.float32):
    shapes = ((b, h, s, d), (b, g, s, d), (b, g, s, d), (b, h, s, d))
    return [jr.normal(jr.PRNGKey(i), shape, dtype)
            for i, shape in enumerate(shapes)]


def _fwd_and_grads(f, q, k, v, g):
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(g)


@pytest.mark.parametrize("b,h,g,s,window,bq,bk", [
    (1, 4, 2, 512, 256, 128, 128),     # groups of 2, window of two tiles
    (2, 4, 1, 512, 128, 128, 128),     # every head reads one kv head
    (1, 2, 2, 512, 200, 128, 128),     # a window that ends inside a tile
    (1, 4, 2, 512, 256, 256, 128),     # a tall tile: diagonal and edge meet
    (1, 2, 1, 512, 384, 128, 256),     # a wide tile
    (1, 2, 1, 512, 64, 128, 128),      # a window inside one tile
    (1, 8, 1, 256, 100, 128, 128),     # eight heads to the group
    (1, 4, 2, 512, None, 128, 128),    # groups, no window
])
def test_groups_and_a_window_match_the_reference(b, h, g, s, window, bq, bk):
    q, k, v, dy = _qkvg(b, h, g, s)
    got = _fwd_and_grads(lambda a, b_, c: FA.flash_attention(
        a, b_, c, causal=True, window=window, block_q=bq, block_k=bk,
        interpret=True), q, k, v, dy)
    want = _fwd_and_grads(lambda a, b_, c: FA.attention_reference(
        a, b_, c, causal=True, window=window), q, k, v, dy)
    assert got[1].shape == q.shape and got[2].shape == k.shape
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a - w)) / jnp.max(jnp.abs(w)))
        assert err < 2e-5, (name, err)


def test_the_reference_itself_keeps_window_keys_and_reads_its_group():
    """Against a loop over heads and rows: row i of head h sees keys
    i - W + 1 .. i of key-value head h // (H/G)."""
    q, k, v, _ = _qkvg(1, 4, 2, 128, d=16)
    got = FA.attention_reference(q, k, v, causal=True, window=48)
    for h in (0, 3):
        for i in (0, 47, 48, 127):
            lo = max(0, i - 47)
            s = (k[0, h // 2, lo:i + 1] @ q[0, h, i]) * 16 ** -0.5
            want = jax.nn.softmax(s) @ v[0, h // 2, lo:i + 1]
            onp.testing.assert_allclose(got[0, h, i], want, atol=1e-5)


def test_tiles_outside_the_window_are_no_grid_steps():
    """With a window the step count grows with the sequence, not with its
    square: a q-block's row of tiles stops W + block_k back."""
    def steps(seq, window, kv_major=False, group=1, bq=128, bk=128):
        return FA._steps(seq // bq, seq // bk, bq, bk, True, kv_major,
                         window, group)[0].size
    assert steps(1024, None) == 36 and steps(2048, None) == 136
    assert steps(1024, 256) == 21 and steps(2048, 256) == 45     # 3 a row
    assert steps(2048, 256) - steps(1024, 256) == 3 * 8
    assert steps(1024, 256, True) == 21
    assert steps(1024, 256, True, group=4) == 4 * 21
    # the real cell: S 8192, W 2048, the default tiles of each kernel
    fwd, dq, dkv = FA._default_blocks(8192, 8192)
    assert steps(8192, 2048, bq=fwd[0], bk=fwd[1]) == 14       # 20 causal
    assert steps(8192, 2048, bq=dq[0], bk=dq[1]) == 7          # 10 causal
    assert steps(8192, 2048, True, 8, *dkv) == 56              # 80 causal
    # dk/dv with groups: a k-block's steps run through the group's heads
    qi, ki = FA._steps(4, 4, 128, 128, True, True, 256, 2)
    assert ki.tolist() == sorted(ki.tolist())
    assert qi[ki == 1].tolist() == [1, 2, 3, 5, 6, 7]


def test_computed_pairs_with_a_window_at_the_cells_shapes():
    """What metrics()['flash'] reports for the cell's sliding layers:
    computed over kept pairs 1.125 for all three kernels (the chunks' lane
    tiles at both edges), against 1.031 causal."""
    kept = FA._kept_pairs(8192, 8192, True, 2048)
    assert kept == 2048 * 2049 // 2 + 6144 * 2048
    for (bq, bk), kv_major in zip(FA._default_blocks(8192, 8192),
                                  (False, False, True)):
        chunk = FA._chunk(bk if kv_major else bq)
        got = FA._computed_pairs(8192, 8192, bq, bk, chunk, True, kv_major,
                                 2048)
        assert 1.0 < got / kept < 1.13
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16)
    FA._CALLS.clear()
    jax.eval_shape(lambda a, b, c: FA.flash_attention(
        a, b, c, causal=True, window=2048, interpret=True), q, k, k)
    (key, entry), = FA._CALLS.items()
    assert key == "2x32x8192x8192x128.bfloat16.causal.kv4.window2048"
    assert entry == ("fwd=2048x1024/1.1249 dq=2048x2048/1.1249 "
                     "dkv=2048x2048/1.1249")


def test_a_window_needs_causal_and_heads_must_divide():
    q, k, v, _ = _qkvg(1, 4, 2, 128)
    with pytest.raises(ValueError, match="causal"):
        FA.flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError, match="share"):
        FA.flash_attention(q[:, :3], k, v, causal=True)


# sha256 of the float32 bytes of (out, dq, dk, dv) at the parent commit, MHA
# [1, 2, 256, 128] bf16 causal, tiles of 128, interpret mode. PARENT_JAXPR
# was taken again once, at PR 33: ``_flash_fwd`` names the two residuals
# (``name[name=flash_out]``, ``name[name=flash_lse]``: identities outside a
# jax.checkpoint), which adds two equations to the traced text and moves the
# letters of the variables after them; nothing else differs from fb3c3da's.
PARENT_BITS = [
    "9b02d974c7d24de4672c1bba74326861fa1fac4b7f592d45365ee3032b5fe8cf",
    "f54bbc72238f8f5ba81bd7e679f053239950b67a187e288be158780807c8dc37",
    "750543bb5089dd9dd3b66042c9dba33e69a5bbf487b3bf9d863de3efac6b94dc",
    "6c1a8921fd3c5a898737ad2bfa0af15050044301bb3a1238ab38993670191747"]
PARENT_JAXPR = \
    "05a371fee20ce7039155fa71881c7490388236c9642b287e252e27a06e2440a3"


def test_without_groups_or_a_window_every_bit_is_the_parents():
    q, k, v, g = (jr.normal(jr.PRNGKey(i), (1, 2, 256, 128), jnp.bfloat16)
                  for i in range(4))

    def f(q, k, v):
        return FA.flash_attention(q, k, v, causal=True, block_q=128,
                                  block_k=128, interpret=True)

    FA._CALLS.clear()
    got = _fwd_and_grads(f, q, k, v, g)
    bits = [hashlib.sha256(onp.asarray(a.astype(jnp.float32)).tobytes())
            .hexdigest() for a in got]
    assert bits == PARENT_BITS
    # the traced program of the two backward kernels, to the letter
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(f, q, k, v)[1](g))(q, k, v)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == PARENT_JAXPR
    # the step tables and the entry of metrics()['flash']
    assert FA._steps(4, 4, 128, 128, True, False)[0].tolist() == [
        0, 1, 1, 2, 2, 2, 3, 3, 3, 3]
    assert FA._steps(4, 4, 128, 128, True, True)[1].tolist() == [
        0, 0, 0, 0, 1, 1, 1, 2, 2, 3]
    assert FA._CALLS == {"1x2x256x256x128.bfloat16.causal":
                         "fwd=128x128/1.4942 dq=128x128/1.4942 "
                         "dkv=128x128/1.4942"}
    # a window as long as the sequence cuts nothing: the same call
    again = FA.flash_attention(q, k, v, causal=True, window=256,
                               block_q=128, block_k=128, interpret=True)
    assert bool(jnp.all(again == got[0]))

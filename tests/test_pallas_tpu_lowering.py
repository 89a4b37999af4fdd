"""Every default-on Pallas kernel cross-lowers for the TPU from the CPU.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas-to-Mosaic
lowering — block-shape rules, memory spaces, the ops Mosaic accepts —
with no chip attached. It takes seconds and keeps a kernel from being
valid in interpret mode only, which is how the fused-BatchNorm stats
kernel shipped: at ResNet-50's last stage its output block was (2, 256).
What the Mosaic compiler itself says (VMEM, layouts) only the chip can
tell; that is chip_smoke.py phase B, over this same table.

The last test goes one step further and COMPILES, for a v5e that is
described and not attached (``jax.experimental.topologies``): what XLA makes
of the state-space mixer's gradient at the published widths, by the bytes
its program moves. It is the only test that can see XLA undo what
``parallel/ssm.py`` does about float32 arrays between the mixer's products.
"""
import importlib
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

BN = importlib.import_module("mxnet_tpu.pallas_kernels.batchnorm_fused")


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The kernels' own dispatch as it runs on a TPU: every backend
    predicate says yes, so the public entry points take the Pallas
    branch (their fit predicates still decide) instead of the jnp one."""
    for mod, name in (("batchnorm_fused", "_use_pallas"),
                      ("quantized_matmul", "_use_pallas"),
                      ("flash_attention", "_use_pallas"),
                      ("compression", "_pallas_ok")):
        monkeypatch.setattr(
            importlib.import_module("mxnet_tpu.pallas_kernels." + mod),
            name, lambda *a: True)


CASES = chip_smoke.kernel_cases()


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_lowers_to_mosaic(as_on_tpu, case):
    name, fn, specs, n_kernels = case[:4]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    assert exported.mlir_module().count(chip_smoke.MOSAIC_CALL) \
        == n_kernels, "%s: a jnp path was taken" % name


@pytest.mark.parametrize("R,C,tr", [
    (6272, 2048, 128),      # ResNet-50 last stage, batch 128: the refusal
    (3136, 2048, 64),       # one partial row per tile
    (25088, 1024, 512),     # pt == 8: lowered before, too
])
def test_bn_row_tiles_under_512_fit_and_lower(as_on_tpu, R, C, tr):
    """A row tile under 512 yields fewer than 8 partial rows per tile;
    with several tiles along the rows that used to break Mosaic's
    (8, 128) block rule for the stats kernel's output."""
    import jax.numpy as jnp
    assert BN._tiles(R, C, 2, 2) == (tr, 256, True)
    x = jax.ShapeDtypeStruct((R, C), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((C,), jnp.float32)
    assert BN.engaged(x, axis=1)
    exported = jax.export.export(
        jax.jit(lambda x_, g_, b_: BN.fused_batch_norm(x_, g_, b_)),
        platforms=["tpu"])(x, g, g)
    assert chip_smoke.MOSAIC_CALL in exported.mlir_module()


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four virtual devices")
@pytest.mark.parametrize("limit,kernels", [(None, 4), (2 ** 34, 3)],
                         ids=["no_limit", "room"])
def test_flash_inside_the_sharded_transformer_step_lowers(
        as_on_tpu, monkeypatch, limit, kernels):
    """GSPMD cannot partition a Mosaic kernel: on a dp x tp mesh the
    TPU lowering of the transformer step refused ("wrap the call in a
    shard_map") — seen first on four real chips, because on the CPU mesh
    attention takes the jnp reference. The step now runs the kernel per
    (batch, head) shard; forward, its remat re-run, dq and dk/dv lower.
    On a device that reports a limit with room the layer remat keeps the
    forward kernel's output and row sums, and the re-run is gone."""
    import jax.numpy as jnp
    import jax.random as jr
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel import transformer as T
    monkeypatch.setattr(T, "_mesh_bytes_limit", lambda mesh: limit)
    mesh = create_mesh(devices=jax.devices()[:4], dp=2, tp=2)
    cfg = T.TransformerConfig(
        vocab_size=256, dim=256, n_layers=1, n_heads=2, ffn_hidden=512,
        max_seq_len=128, dtype="bfloat16", attn_mode="local", loss_chunks=2)
    _, step_fn = T.make_train_step(cfg, mesh)
    with mesh.mesh:
        params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                                jr.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((4, 128), jnp.int32)
        exported = jax.export.export(step_fn, platforms=["tpu"])(
            (params, params), toks, toks)
    assert exported.mlir_module().count(chip_smoke.MOSAIC_CALL) == kernels


def test_flash_with_groups_and_a_window_lowers_at_the_trinity_cells_shape(
        as_on_tpu):
    """[2, 32 / 4, 8192, 128] bf16, window 2048, each kernel on its default
    tile: forward, dq and the dk/dv that sums over a group's eight heads."""
    import jax.numpy as jnp
    FA = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16)

    def fwd_and_grads(q, k, v, g):
        out, vjp = jax.vjp(lambda a, b, c: FA.flash_attention(
            a, b, c, causal=True, window=2048), q, k, v)
        return (out,) + vjp(g)

    exported = jax.export.export(jax.jit(fwd_and_grads),
                                 platforms=["tpu"])(q, kv, kv, q)
    assert [a.shape for a in exported.out_avals] == [
        q.shape, q.shape, kv.shape, kv.shape]
    assert exported.mlir_module().count(chip_smoke.MOSAIC_CALL) == 3


def test_the_grouped_products_lower_at_the_trinity_cells_shape(monkeypatch):
    """139264 rows (16384 tokens x 8 slots and a tile of padding an expert)
    of width 2048 against 32 matrices of 2048 x 1024: forward, dx, dw."""
    import jax.numpy as jnp
    G = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(G, "_use_pallas", lambda: True)
    rows = 16384 * 8 + 32 * G.TILE
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((32, 2048, 1024), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((32,), jnp.int32)
    group_of = jax.ShapeDtypeStruct((rows // G.TILE,), jnp.int32)

    def grads(x, w, sizes, group_of):
        out, vjp = jax.vjp(lambda x, w: G.grouped_matmul(
            x, w, sizes, group_of), x, w)
        return (out,) + vjp(out)

    exported = jax.export.export(jax.jit(grads), platforms=["tpu"])(
        x, w, sizes, group_of)
    assert [a.shape for a in exported.out_avals] == [
        (rows, 1024), x.shape, w.shape]
    assert exported.mlir_module().count(chip_smoke.MOSAIC_CALL) == 3


@pytest.mark.parametrize("rows,k,n,groups", [
    (84224, 4096, 768, 9),      # granite-4.0-h-small's share: 8192 x 10
    (36864, 2048, 2048, 16),    # ZAYA1-8B's whole layer: 32768 x 1
])
def test_the_gated_pair_lowers_at_the_expert_cells_widths(monkeypatch, rows,
                                                           k, n, groups):
    """``grouped_glu`` and its gradient: ``mx_gmm_glu_fwd`` (h, a and b),
    ``mx_gmm_glu_dx`` and ``mx_gmm_dw`` twice, each asking for VMEM under
    the cap."""
    import jax.numpy as jnp
    G = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(G, "_use_pallas", lambda: True)
    assert G.glu_fits(k, n, jnp.bfloat16)
    assert G._glu_vmem(k, n, 2) + G._VMEM_SLACK < 100 << 20
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32)
    group_of = jax.ShapeDtypeStruct((rows // G.TILE,), jnp.int32)

    def grads(x, wg, wu, sizes, group_of):
        h, vjp = jax.vjp(lambda x, wg, wu: G.grouped_glu(
            x, wg, wu, sizes, group_of), x, wg, wu)
        return (h,) + vjp(h)

    exported = jax.export.export(jax.jit(grads), platforms=["tpu"])(
        x, w, w, sizes, group_of)
    assert [a.shape for a in exported.out_avals] == [
        (rows, n), x.shape, w.shape, w.shape]
    text = exported.mlir_module()
    assert text.count(chip_smoke.MOSAIC_CALL) == 4
    for name in ("mx_gmm_glu_fwd", "mx_gmm_glu_dx", "mx_gmm_dw"):
        assert name in text, name


def test_the_expert_shares_row_movements_lower_at_the_trinity_cells_shape():
    """16384 tokens x 8 slots of width 2048 in bfloat16, 32 experts held:
    dispatch, combine and both transposes through ``mx_moe_pack``,
    ``mx_moe_gather`` and ``mx_moe_sum``: a pack and a movement each."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import expert as X
    from mxnet_tpu.pallas_kernels import moe_rows
    T, k, D, held, tile = 16384, 8, 2048, 32, 256
    rows = X.buffer_rows(T, k, held, tile)
    assert rows == 139264 and moe_rows.fits(D, jnp.bfloat16, True)
    how = (tile, False)

    def movements(experts, x, ys, w, g_rows, g_tokens):
        plan = X._plan(experts, 0, held, tile)
        lists, fetched = moe_rows.tile_lists(plan.row_of, plan.held, rows)
        plan = plan._replace(lists=lists, fetched=fetched)
        xs, back = jax.vjp(lambda x: X._dispatch(x, plan, how), x)
        y, back_y = jax.vjp(lambda ys, w: X._combine(
            ys, w, plan, how, jnp.bfloat16), ys, w)
        return (xs, y) + back(g_rows) + back_y(g_tokens)

    s = jax.ShapeDtypeStruct
    exported = jax.export.export(jax.jit(movements), platforms=["tpu"])(
        s((T, k), jnp.int32), s((T, D), jnp.bfloat16),
        s((rows, D), jnp.bfloat16), s((T, k), jnp.float32),
        s((rows, D), jnp.bfloat16), s((T, D), jnp.bfloat16))
    assert [a.shape for a in exported.out_avals] == [
        (rows, D), (T, D), (T, D), (rows, D), (T, k)]
    text = exported.mlir_module()
    assert text.count(chip_smoke.MOSAIC_CALL) == 8
    for name in ("mx_moe_pack", "mx_moe_gather", "mx_moe_sum"):
        assert name in text, name


@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a described v5e as a sharding; the tests that ask for it
    are skipped where no such topology can be described. Made here and not
    at import: one process at a time may load the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_mixers_gradient_moves_its_arrays_in_bfloat16_on_a_v5e(one_v5e):
    """granite-4.0-h-small's mixer (4096 -> 128 heads of 64, state 128, four
    taps) on 1 x 8192 tokens in bfloat16: its vjp under ``jax.checkpoint``,
    compiled for the described chip. At the parent of PR 35 the program read
    14.72 GB and held 2.07 GB of temporaries: the conv kept a padded float32
    copy of x and four float32 products, and XLA hoisted the gate's cast
    above the copies that take the scan's blocks to [tokens, channels]. Now
    10.71 GB and 0.97 GB (of the bytes 0.83 GB are the cost analysis
    counting ``ds``, the one float32 array the conv's backward writes, once
    for each of its four shifted reads); either half undone reads 12.6 GB or
    more."""
    import re
    import types
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.parallel import ssm
    cfg = types.SimpleNamespace(dim=4096, ssm_heads=128, ssm_head_size=64,
                                ssm_state=128, ssm_conv=4, ssm_chunk=256,
                                norm_eps=1e-5)

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_v5e)

    leaves = {n: spec(shape)
              for n, (shape, _, _) in ssm.mixer_leaves(cfg).items()}
    h = spec((1, 8192, 4096))

    def grads(h, lp, dout):
        return jax.vjp(jax.checkpoint(
            lambda h, lp: ssm.mixer(h, lp, cfg)), h, lp)[1](dout)

    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out, and the warning with it
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(grads).lower(h, leaves, h).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert compiled.cost_analysis()["bytes accessed"] < 11.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
    # the entry computation's own instructions (a fusion's inside never
    # reaches HBM) under the two scopes: none is float32 [tokens, channels],
    # in the scan's blocks or out of them
    entry = compiled.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    wide = re.compile(r" = \(?f32\[1,8192,8448\]| = \(?f32\[1,8192,8192\]"
                      r"| = \(?f32\[1,32,256,16,8,64\]")
    under = [line for line in entry.splitlines()
             if "mx.ssm_conv" in line or "mx.ssm_gate" in line]
    assert len(under) > 10
    assert [line.strip()[:120] for line in under if wide.search(line)] == []


def _compiled_for(one_v5e, fn, *args):
    """fn compiled for the described chip, kept out of the persistent
    cache (a program for a described chip cannot be read back from it)."""
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def test_the_latent_attention_cells_kernels_compile_on_a_v5e(one_v5e,
                                                             monkeypatch):
    """GigaChat3.1's share, compiled for the described chip: the flash
    kernels at one head size of 192 lanes (q and k 128 + 64, v 192; 8 heads
    held, 4096 positions, causal, YaRN's scale), unpadded; and the expert
    products' gradient at hidden 7168 against 8 experts of 2048, whose
    ``mx_gmm_dw`` blocks ([7168, 2048] and [2048, 7168]) miss VMEM whole and
    go in column blocks of 1024 and 3584."""
    import jax.numpy as jnp
    FA = importlib.import_module("mxnet_tpu.pallas_kernels.flash_attention")
    G = importlib.import_module("mxnet_tpu.pallas_kernels.grouped_matmul")
    monkeypatch.setattr(FA, "_use_pallas", lambda: True)
    monkeypatch.setattr(G, "_use_pallas", lambda: True)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    def attention(q, k, v, g):
        out, vjp = jax.vjp(lambda a, b, c: FA.flash_attention(
            a, b, c, causal=True, scale=0.1446796), q, k, v)
        return (out,) + vjp(g)

    q = spec(1, 8, 4096, 192)
    text = _compiled_for(one_v5e, attention, q, q, q, q).as_text()
    for name in ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"):
        assert name in text, name
    assert "bf16[1,8,4096,256]" not in text     # no padding to 256 lanes
    assert (G._dw_cols(7168, 2048, 2), G._dw_cols(2048, 7168, 2)) == (
        1024, 3584)
    rows = 4096 * 8 + 8 * G.TILE

    def dws(x, h, dy, dh, group_of, used):
        # w_gate's and w_down's gradients, as the share's backward takes them
        return (G._dw(x, dh, group_of, used, 8, False),
                G._dw(h, dy, group_of, used, 8, False))

    text = _compiled_for(
        one_v5e, dws, spec(rows, 7168), spec(rows, 2048), spec(rows, 7168),
        spec(rows, 2048), spec(rows // G.TILE, dtype=jnp.int32),
        spec(1, dtype=jnp.int32)).as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2

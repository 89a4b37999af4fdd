#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments needed. It refuses to run unless JAX's default
backend is a TPU (no CPU fallback, no toy-size branch), then drives the
main paths once through the entry points a user calls, at the full width
of the models the repo supports, with random weights made from a seed:

  A  Gluon ResNet-50 through ``Trainer.fuse_step`` (the system's own
     train step), batch 128, bf16; then everything donation can break:
     read-back, an eval forward, save -> load into a fresh net/trainer ->
     step again.
  B  every Pallas kernel that is on by default on a TPU, compiled
     non-interpret at the shapes the models use and compared with its
     jnp reference.
  C  the flash kernels inside a real step: the flagship transformer
     config through ``parallel.transformer.make_train_step``.
  D  four chips in one process (only where ``jax.device_count() >= 4``):
     the fused step in shard_map mode (dp=4) and in GSPMD mode
     (dp=2 x tp=2), and phase C's transformer at dp=2 x tp=2.

Any failed check in any phase is an exception: the process exits non-zero
and prints no result. On success the last two lines of standard output
are compact JSON objects: first the run's record (per-phase results, step
modes, set-up vs steady seconds, compile-cache directory, native library;
the times are information about this run on the named device, not a
claim), then, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it. That last line is what the driver reads
and it carries no other key.

The phases are functions of their sizes so that tests/test_chip_smoke.py
can run the same control flow at toy sizes on the CPU (kernels in
interpret mode). This script itself never picks a toy size.
"""
import argparse
import functools
import json
import os
import sys
import tempfile
import time

import numpy as np

MOSAIC_CALL = "tpu_custom_call"

# phase B: kernel x shape, as the models use them. tests/
# test_pallas_tpu_lowering.py cross-lowers exactly this table on the CPU.
FLASH_SHAPE = (12, 32, 2048, 128)          # bench transformer, bf16 causal
FLASH_LONG_SHAPE = (1, 16, 65536, 128)     # README long-context claim
# the attention of BENCHMARK.json's two cells (baichuan_7b.l5-seq2048,
# -seq16384): each kernel's own tile shape has to pass Mosaic there
FLASH_CELL_SHAPES = ((8, 32, 2048, 128), (1, 32, 16384, 128))
# ResNet-50's channels-last stage shapes at batch 128, and one Dense case
BN_SHAPES = (((401408, 256), "bfloat16"), ((100352, 512), "bfloat16"),
             ((25088, 1024), "bfloat16"), ((6272, 2048), "bfloat16"),
             ((256, 128), "float32"))
QMM_SHAPE = (128, 4096, 4096)              # (M, K, N) int8
TWOBIT_N = 1 << 20
OFF_BY_DEFAULT = ("optimizer_apply (MXTPU_FUSED_APPLY, default off)",
                  "conv_fused (resnet*(fuse=), default off)")

# phase C: bench.py's flagship transformer
FLAGSHIP = dict(dim=4096, heads=32, ffn=16384, vocab=32000, seq=2048,
                batch=12, layers=5, loss_chunks=8, dtype="bfloat16")


_T0 = time.perf_counter()


def say(msg):
    print("[chip_smoke %6.1fs] %s" % (time.perf_counter() - _T0, msg),
          flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def platforms_of(nd):
    """Platforms of the devices that really hold an NDArray's buffer.
    ``NDArray.context`` cannot be used for this: ``mx.tpu()`` resolves to
    a CPU device where there is no accelerator and still prints tpu(0)."""
    return {d.platform for d in nd.dlpack.devices()}


def rel_err(got, want):
    """max|got - want| relative to max|want|, reduced on the device (the
    arrays are hundreds of MB). Integer outputs compare for equality."""
    import jax.numpy as jnp
    check(got.shape == want.shape and got.dtype == want.dtype,
          "%s%s != reference %s%s" % (got.dtype, got.shape, want.dtype,
                                      want.shape))
    if jnp.issubdtype(got.dtype, jnp.integer):
        return 0.0 if bool(jnp.array_equal(got, want)) else float("inf")
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    check(bool(jnp.isfinite(got).all()), "non-finite values in the output")
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def fused_stats_delta(before):
    import mxnet_tpu as mx
    now = mx.profiler.metrics()["fused_step"]
    return {k: now[k] - before.get(k, 0) for k in now}


# ---------------------------------------------------------------------------
# Phase A: Gluon model through Trainer.fuse_step
# ---------------------------------------------------------------------------

def _image_batch(rs, batch, image, classes, dtype, platform):
    """One seeded (images, labels) batch created on mx.tpu()."""
    import mxnet_tpu as mx
    x = mx.nd.array(rs.rand(batch, 3, image, image).astype("float32"),
                    ctx=mx.tpu(), dtype=dtype)
    y = mx.nd.array(rs.randint(0, classes, (batch,)).astype("float32"),
                    ctx=mx.tpu())
    check(platforms_of(x) == {platform}, "batch lives on %s"
          % platforms_of(x))
    return x, y


def resnet50(classes=1000):
    from mxnet_tpu.gluon.model_zoo import vision
    return vision.resnet50_v1(classes=classes)


def _make_net(make_net, classes, platform, x):
    """initialize on mx.tpu() -> hybridize -> cast -> one predict-mode
    forward (finishes deferred init; the same compiled forward serves the
    eval check after training)."""
    import mxnet_tpu as mx
    net = make_net(classes=classes)
    net.initialize(ctx=mx.tpu())
    net.hybridize()
    net.cast(str(x.dtype))
    out = net(x)
    check(platforms_of(out) == {platform},
          "eval forward ran on %s" % platforms_of(out))
    return net


def _trainer(net):
    from mxnet_tpu import gluon
    return gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.01, "momentum": 0.9})


def _optimizer_states(trainer):
    """Every optimizer-state NDArray of a trainer (there is no public
    accessor; save_states serializes the same store)."""
    import jax
    from mxnet_tpu.ndarray import NDArray
    return [s for s in jax.tree_util.tree_leaves(
        list(trainer._updater.states.values()),
        is_leaf=lambda s: isinstance(s, NDArray))
        if isinstance(s, NDArray)]


def _run_steps(step, x, y, n, platform):
    """n calls of a fused step on one batch -> (modes, mean losses,
    wall seconds per call). Each call is waited for."""
    modes, losses, walls = [], [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(x, y)
        val = float(loss.asnumpy().astype(np.float32).mean())
        walls.append(time.perf_counter() - t0)
        check(np.isfinite(val), "loss is not finite: %r" % val)
        check(platforms_of(loss) == {platform},
              "loss lives on %s" % platforms_of(loss))
        check(not step.last_mode.startswith("fallback"),
              "step fell back: %s (%r)" % (step.last_mode,
                                           step.last_trace_error))
        modes.append(step.last_mode)
        losses.append(val)
    return modes, losses, walls


def _check_modes(modes):
    check(modes[:2] == ["eager-warming", "compile"]
          and len(modes) > 2 and set(modes[2:]) == {"fused"},
          "step modes %s, want eager-warming, compile, fused..." % modes)


def _check_on_platform(net, trainer, platform):
    """Read every parameter and optimizer state back to the host (a
    donated-and-deleted buffer raises here) and check where it lives."""
    n = 0
    for name, p in net.collect_params().items():
        check(platforms_of(p.data()) == {platform},
              "param %s lives on %s" % (name, platforms_of(p.data())))
        check(np.isfinite(p.data().asnumpy().astype(np.float32)).all(),
              "param %s is not finite" % name)
        n += 1
    states = _optimizer_states(trainer)
    check(states, "trainer holds no optimizer state")
    for s in states:
        check(platforms_of(s) == {platform},
              "optimizer state lives on %s" % platforms_of(s))
        s.asnumpy()
    return n, len(states)


def phase_a(platform, make_net=resnet50, classes=1000, image=224,
            batch=128, dtype="bfloat16", steps=6, seed=0):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    t_start = time.perf_counter()
    before = mx.profiler.metrics()["fused_step"]
    mx.random.seed(seed)
    x, y = _image_batch(np.random.RandomState(seed), batch, image, classes,
                        dtype, platform)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    net = _make_net(make_net, classes, platform, x)
    init_s = time.perf_counter() - t_start
    trainer = _trainer(net)
    step = trainer.fuse_step(lambda a, b: loss_fn(net(a), b))
    modes, losses, walls = _run_steps(step, x, y, steps, platform)
    _check_modes(modes)
    setup_s = time.perf_counter() - t_start - sum(walls[3:])
    check(losses[-1] < losses[0], "loss did not fall: %s" % losses)
    say("A: init+eval forward %.1fs; modes %s walls %s losses %.4f -> %.4f"
        % (init_s, modes, [round(w, 2) for w in walls], losses[0],
           losses[-1]))

    # what donation can break
    n_params, n_states = _check_on_platform(net, trainer, platform)
    out = net(x)
    check(np.isfinite(out.asnumpy().astype(np.float32)).all(),
          "eval forward after training is not finite")
    with tempfile.TemporaryDirectory() as td:
        net.save_parameters(os.path.join(td, "net.params"))
        trainer.save_states(os.path.join(td, "trainer.states"))
        say("A: read-back, eval forward and save done")
        net2 = _make_net(make_net, classes, platform, x)
        net2.load_parameters(os.path.join(td, "net.params"), ctx=mx.tpu())
        trainer2 = _trainer(net2)
        trainer2.load_states(os.path.join(td, "trainer.states"))
        say("A: fresh net and trainer loaded")
    step2 = trainer2.fuse_step(lambda a, b: loss_fn(net2(a), b))
    modes2, losses2, walls2 = _run_steps(step2, x, y, 3, platform)
    _check_modes(modes2)
    check(losses2[0] < losses[0],
          "the loaded net starts at loss %.4f, the fresh one started at "
          "%.4f: the trained weights did not load" % (losses2[0], losses[0]))
    _check_on_platform(net2, trainer2, platform)
    say("A: save -> load -> step: modes %s walls %s loss %.4f"
        % (modes2, [round(w, 2) for w in walls2], losses2[-1]))

    stats = fused_stats_delta(before)
    for k in ("fallbacks", "attr_errors", "health_errors", "retraces"):
        check(stats[k] == 0, "fused_step.%s == %d, want 0 (%s)"
              % (k, stats[k], stats))
    rec = mx.profiler.metrics()["compile"]["fused_step"]
    check(rec.get("flops") and rec.get("modeled_compute_us"),
          "the compile record carries no cost model (flops=%r, "
          "modeled_compute_us=%r): comm_model missing or AOT analysis "
          "failed" % (rec.get("flops"), rec.get("modeled_compute_us")))
    return {"ok": True, "model": make_net.__name__, "batch": batch,
            "dtype": dtype,
            "modes": modes, "modes_after_load": modes2,
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "loss_after_load": round(losses2[-1], 4),
            "params": n_params, "optimizer_states": n_states,
            "fused_step": stats,
            "init_and_eval_forward_s": round(init_s, 1),
            "eager_step_s": round(walls[0], 1),
            "compile_step_s": round(walls[1], 1),
            "setup_s": round(setup_s, 1),
            "steady_step_s": round(float(np.median(walls[3:])), 4)}


# ---------------------------------------------------------------------------
# Phase B: default-on kernels vs their references
# ---------------------------------------------------------------------------

def kernel_cases(flash_shape=FLASH_SHAPE, flash_long_shape=FLASH_LONG_SHAPE,
                 flash_cell_shapes=FLASH_CELL_SHAPES,
                 bn_shapes=BN_SHAPES, qmm_shape=QMM_SHAPE,
                 twobit_n=TWOBIT_N, flash_dtype="bfloat16",
                 interpret=False):
    """[(name, fn, arg_specs, n_kernels, ref_fn, tolerances)] for every
    kernel that is on by default on a TPU. ``fn`` goes through the
    public kernel entry point (its own dispatch and fit predicates
    included) and must lower to ``n_kernels`` Mosaic calls; ``ref_fn``
    is the repo's jnp reference on the same arguments, or None where
    the case only has to compile and run. ``tolerances`` are per
    output, relative to the reference's max magnitude (0 = equal)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu.pallas_kernels as PK
    from mxnet_tpu.pallas_kernels.batchnorm_fused import batchnorm_reference
    from mxnet_tpu.pallas_kernels.flash_attention import attention_reference
    from mxnet_tpu.pallas_kernels.quantized_matmul import \
        quantized_matmul_reference
    S = jax.ShapeDtypeStruct
    cases = []

    def fwd_bwd(f):
        def run(*args):
            *ins, ct = args
            out, vjp = jax.vjp(f, *ins)
            return (out,) + tuple(vjp(ct))
        return run

    # flash attention: forward + both backward kernels (dq; dk/dv)
    flash = fwd_bwd(functools.partial(PK.flash_attention, causal=True,
                                      interpret=interpret))
    flash_ref = fwd_bwd(functools.partial(attention_reference, causal=True))
    low = jnp.dtype(flash_dtype).itemsize < 4
    # a reference only where one batch element's score matrix fits
    for shape, with_ref in ([(flash_shape, True), (flash_long_shape, False)]
                            + [(s, s[2] <= 2048) for s in flash_cell_shapes]):
        if shape is None:
            continue
        cases.append(("flash_attention fwd+bwd %s %s causal"
                      % (list(shape), flash_dtype),
                      flash, (S(shape, jnp.dtype(flash_dtype)),) * 4, 3,
                      flash_ref if with_ref else None,
                      ((3e-2,) + (5e-2,) * 3 if low else (1e-3,) * 4)
                      if with_ref else None))

    # fused BatchNorm(+relu): stats + apply forward, reduce + dx backward
    def bn_fn(kernel):
        def run(x, g, b, ct):
            (out, mean, var), vjp = jax.vjp(kernel, x, g, b)
            return (out, mean, var) + tuple(vjp(
                (ct, jnp.zeros_like(mean), jnp.zeros_like(var))))
        return run

    bn = bn_fn(lambda x, g, b: PK.fused_batch_norm(
        x, g, b, act="relu", interpret=interpret))
    bn_ref = bn_fn(lambda x, g, b: batchnorm_reference(
        x, g, b, act="relu"))
    for shape, dt in bn_shapes:
        xs, gs = S(shape, jnp.dtype(dt)), S((shape[1],), jnp.float32)
        lo = jnp.dtype(dt).itemsize < 4
        cases.append(("fused_batch_norm fwd+bwd %s %s" % (list(shape), dt),
                      bn, (xs, gs, gs, xs), 4, bn_ref,
                      (2e-2 if lo else 1e-5, 1e-5, 1e-4,
                       3e-2 if lo else 1e-4, 2e-3, 2e-3)))

    # int8 matmul, raw int32 accumulator and fused per-channel dequant
    if qmm_shape is not None:
        M, K, N = qmm_shape
        cases.append(("quantized_matmul %s int8" % list(qmm_shape),
                      lambda x, w, s: (
                          PK.quantized_matmul(x, w, interpret=interpret),
                          PK.quantized_matmul(x, w, s, interpret=interpret)),
                      (S((M, K), jnp.int8), S((K, N), jnp.int8),
                       S((N,), jnp.float32)), 2,
                      lambda x, w, s: (
                          quantized_matmul_reference(x, w),
                          quantized_matmul_reference(x, w, s)),
                      (0, 1e-6)))

    # 2-bit gradient compression, both directions
    def twobit(quant, dequant):
        def run(g, r):
            words, newr = quant(g, r)
            return words, newr, dequant(words)
        return run

    vec = S((twobit_n,), jnp.float32)
    cases.append(("quantize_2bit/dequantize_2bit [%d] f32" % twobit_n,
                  twobit(lambda g, r: PK.quantize_2bit(
                             g, r, interpret=interpret),
                         lambda w: PK.dequantize_2bit(
                             w, twobit_n, interpret=interpret)),
                  (vec, vec), 2,
                  twobit(PK.quantize_2bit_jnp,
                         lambda w: PK.dequantize_2bit_jnp(w, twobit_n)),
                  (0, 0, 0)))
    return cases


def _random_args(specs, seed):
    """Seeded device arrays for a case's argument specs."""
    import jax
    import jax.numpy as jnp
    out = []
    for i, s in enumerate(specs):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out.append(jax.random.randint(key, s.shape, -127, 128,
                                          jnp.int32).astype(s.dtype))
        else:
            out.append(jax.random.normal(key, s.shape,
                                         jnp.float32).astype(s.dtype))
    return out


def phase_b(interpret=False, seed=0, **sizes):
    """Compile each case (asserting the Mosaic custom call is in what is
    compiled, unless interpreted), run it, and compare with the
    reference. A reference too large for the device at the kernel's own
    batch (the flash score matrix) is compared on batch element 0."""
    import jax
    results = {}
    for name, fn, specs, n_kernels, ref_fn, tols in kernel_cases(
            interpret=interpret, **sizes):
        args = _random_args(specs, seed)
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*args)
        if not interpret:
            found = lowered.as_text().count(MOSAIC_CALL)
            check(found == n_kernels,
                  "%s: %d Mosaic call(s) in the lowered program, want %d —"
                  " a reference path was taken" % (name, found, n_kernels))
        compiled = lowered.compile()
        outs = jax.block_until_ready(compiled(*args))
        entry = {"compile_and_run_s": round(time.perf_counter() - t0, 1),
                 "mosaic": not interpret}
        if ref_fn is None:
            for o in outs:
                check(bool(jax.numpy.isfinite(o).all()),
                      "%s: non-finite output" % name)
        else:
            if name.startswith("flash"):
                args = [a[:1] for a in args]
                outs = [o[:1] for o in outs]
            refs = jax.jit(ref_fn)(*args)
            errs = [rel_err(o, r) for o, r in zip(outs, refs)]
            for e, tol in zip(errs, tols):
                check(e <= tol, "%s: error %g exceeds %g (all outputs: %s)"
                      % (name, e, tol, errs))
            entry["max_rel_err"] = float("%.3g" % max(errs))
        results[name] = entry
        say("B: %s %s" % (name, entry))
    return {"ok": True, "kernels": results,
            "not_run_off_by_default": list(OFF_BY_DEFAULT)}


# ---------------------------------------------------------------------------
# Phase C: the flash kernels inside a real transformer step
# ---------------------------------------------------------------------------

def phase_c(platform, dim, heads, ffn, vocab, seq, batch, layers,
            loss_chunks, dtype, steps=3, learning_rate=1.0, seed=0,
            mesh_axes=None, devices=None, require_mosaic=True):
    """``parallel.transformer.make_train_step`` on a mesh of ``devices``
    (default: one device), AOT-compiled once so the program that runs is
    the program inspected. Returns the record plus the compiled HLO."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel import transformer as T
    t_start = time.perf_counter()
    mesh_axes = mesh_axes or {"dp": 1}
    devices = devices if devices is not None else jax.devices()[:1]
    cfg = T.TransformerConfig(
        vocab_size=vocab, dim=dim, n_layers=layers, n_heads=heads,
        ffn_hidden=ffn, max_seq_len=seq, dtype=dtype, attn_mode="local",
        loss_chunks=loss_chunks)
    mesh = create_mesh(devices=devices, **mesh_axes)
    init_fn, step_fn = T.make_train_step(cfg, mesh,
                                         learning_rate=learning_rate)
    rs = np.random.RandomState(seed)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(seed))
        toks = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
        tgts = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
        compiled = step_fn.lower(state, toks, tgts).compile()
        hlo = compiled.as_text()
        if require_mosaic:
            check(MOSAIC_CALL in hlo,
                  "no Mosaic custom call in the compiled transformer step:"
                  " attention took the jnp reference")
        losses, walls = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, loss = compiled(state, toks, tgts)
            losses.append(float(loss))
            walls.append(time.perf_counter() - t0)
    check(all(np.isfinite(losses)), "transformer loss not finite: %s"
          % losses)
    check(losses[-1] < losses[0], "transformer loss did not fall: %s"
          % losses)
    leaves = jax.tree_util.tree_leaves(state)
    for leaf in leaves:
        check({d.platform for d in leaf.devices()} == {platform},
              "transformer state lives on %s" % leaf.devices())
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(state[0]))
    rec = {"ok": True, "mesh": mesh_axes, "layers": layers, "dim": dim,
           "seq": seq, "batch": batch, "params_m": round(n_params / 1e6, 1),
           "losses": [round(v, 4) for v in losses],
           "mosaic_in_hlo": MOSAIC_CALL in hlo,
           "setup_s": round(time.perf_counter() - t_start - sum(walls[1:]),
                            1),
           "steady_step_s": round(float(np.median(walls[1:])), 4)}
    say("C: %s" % rec)
    return rec, hlo, leaves


# ---------------------------------------------------------------------------
# Phase D: four chips, one process
# ---------------------------------------------------------------------------

def _spread(arrays, n):
    """Every array's sharding spans n devices."""
    for a in arrays:
        check(len(a.sharding.device_set) == n,
              "array of shape %s spans %d device(s), want %d"
              % (a.shape, len(a.sharding.device_set), n))


def phase_d(platform, n=4, make_net=resnet50, classes=1000, image=224,
            batch=128, dtype="bfloat16", dense=(1024, 4096, 1024),
            transformer=None, seed=0):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import create_mesh
    devs = jax.devices()[:n]
    before = mx.profiler.metrics()["fused_step"]
    out = {"devices": [str(getattr(d, "coords", d.id)) for d in devs]}
    rs = np.random.RandomState(seed)
    loss_ce = gluon.loss.SoftmaxCrossEntropyLoss()

    # D1: phase A's model, shard_map mode over dp=n
    x, y = _image_batch(rs, batch, image, classes, dtype, platform)
    net = _make_net(make_net, classes, platform, x)
    trainer = _trainer(net)
    step = trainer.fuse_step(lambda a, b: loss_ce(net(a), b),
                             mesh=create_mesh(devices=devs, dp=n))
    modes, losses, _ = _run_steps(step, x, y, 4, platform)
    _check_modes(modes)
    check(losses[-1] < losses[0], "dp=%d loss did not fall: %s"
          % (n, losses))
    _spread([p.data().dlpack for p in net.collect_params().values()], n)
    hlo = step.last_program()[1]
    check(hlo and "all-reduce" in hlo, "no all-reduce in the dp=%d step" % n)
    out["dp%d_shard_map" % n] = {"modes": modes, "losses":
                                 [round(v, 4) for v in losses]}
    say("D: dp=%d shard_map step %s" % (n, out["dp%d_shard_map" % n]))

    # D2: a prefix-named dense net in GSPMD mode, dp x tp with rules
    d_in, d_hid, d_out = dense
    mlp = nn.HybridSequential()
    mlp.add(nn.Dense(d_hid, activation="relu", in_units=d_in, prefix="d0_"))
    mlp.add(nn.Dense(d_out, in_units=d_hid, prefix="d1_"))
    mlp.initialize(ctx=mx.tpu())
    mlp.hybridize()
    tr2 = gluon.Trainer(mlp.collect_params(), "sgd",
                        {"learning_rate": 0.05, "momentum": 0.9})
    loss_l2 = gluon.loss.L2Loss()
    step2 = tr2.fuse_step(
        lambda a, b: loss_l2(mlp(a), b),
        mesh=create_mesh(devices=devs, dp=n // 2, tp=2),
        rules=[("d0.*weight$", ("tp", None)), ("d0.*bias$", ("tp",)),
               ("d1.*weight$", (None, "tp"))])
    xd = mx.nd.array(rs.rand(64, d_in).astype("float32"), ctx=mx.tpu())
    yd = mx.nd.array(rs.rand(64, d_out).astype("float32"), ctx=mx.tpu())
    modes2, losses2, _ = _run_steps(step2, xd, yd, 4, platform)
    _check_modes(modes2)
    check(losses2[-1] < losses2[0], "GSPMD loss did not fall: %s" % losses2)
    check(step2.matched_step_shardings() is True,
          "GSPMD step: output shardings do not match input shardings")
    _spread([p.data().dlpack for p in mlp.collect_params().values()], n)
    hlo2 = step2.last_program()[1]
    check(hlo2 and "all-reduce" in hlo2, "no all-reduce in the GSPMD step")
    out["dp%dxtp2_gspmd" % (n // 2)] = {
        "modes": modes2, "losses": [round(v, 4) for v in losses2],
        "matched_step_shardings": True}
    say("D: GSPMD step %s" % out["dp%dxtp2_gspmd" % (n // 2)])

    stats = fused_stats_delta(before)
    for k in ("fallbacks", "attr_errors", "health_errors", "retraces",
              "mesh_fallbacks"):
        check(stats[k] == 0, "fused_step.%s == %d, want 0 (%s)"
              % (k, stats[k], stats))
    out["fused_step"] = stats

    # D3: phase C's transformer, dp x tp
    if transformer is not None:
        rec, hlo3, leaves = phase_c(
            platform, mesh_axes={"dp": n // 2, "tp": 2}, devices=devs,
            **transformer)
        check("all-reduce" in hlo3, "no all-reduce in the transformer step")
        _spread(leaves, n)
        out["transformer"] = rec

    # the work is really spread: every device holds live buffers
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs] \
        if platform != "cpu" else None
    if in_use is not None:
        check(all(b > 0 for b in in_use),
              "bytes_in_use per device: %s" % in_use)
        out["bytes_in_use"] = in_use
    out["ok"] = True
    return out


# ---------------------------------------------------------------------------

def report(device, record):
    """The two result lines, printed only after every phase passed: the
    run's record, then — last on stdout — the line the driver reads, which
    holds ``ok`` and ``device`` (platform, kind, count) and no other key."""
    compact = functools.partial(json.dumps, separators=(",", ":"))
    print(compact(dict(record, record="chip_smoke")), flush=True)
    print(compact({"ok": True, "device": device}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="ABCD",
                    help="subset of ABCD to run (default: all; D needs "
                         "four devices and is skipped otherwise)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit("chip_smoke.py needs a TPU: JAX's default backend here "
                 "is %r (jax %s). Nothing was run." % (platform,
                                                      jax.__version__))
    # the package comes before the first line of output: beside
    # chip_smoke.py alone there is nothing to run and nothing to report
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import compile_cache
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("device %s jax %s" % (device, jax.__version__))
    cache_dir = mx.runtime.use_compilation_cache()
    check(not compile_cache.enabled(),
          "the AOT executable cache (MXTPU_COMPILE_CACHE_DIR) must be "
          "off in the smoke run")
    native = "libmxnet_tpu.so" \
        if mx.runtime.Features().is_enabled("NATIVE_ENGINE") \
        else "pure-python fallback"
    say("compile cache %s; native library: %s" % (cache_dir, native))

    phases = {}
    if "A" in args.phases:
        phases["A"] = phase_a(platform)
    if "B" in args.phases:
        phases["B"] = phase_b()
    if "C" in args.phases:
        phases["C"] = phase_c(platform, **FLAGSHIP)[0]
    if "D" in args.phases:
        if device["count"] >= 4:
            phases["D"] = phase_d(
                platform, transformer=dict(FLAGSHIP, layers=2, batch=8))
        else:
            phases["D"] = "skipped: %d device(s), needs 4" % device["count"]
            say("D: " + phases["D"])

    report(device, {
        "jax": jax.__version__, "phases": phases,
        "compile_cache_dir": cache_dir, "native_lib": native,
        "total_s": round(time.perf_counter() - t_start, 1)})


if __name__ == "__main__":
    main()

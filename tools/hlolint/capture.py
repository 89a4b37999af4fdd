"""hlolint artifact capture: the bridge from compiled programs to rules.

An **artifact** is one plain dict — picklable, executable-free:

    {"name": "fused_step",            # compiling subsystem
     "sig":  "fused_step:59ea9d0e",   # the roofline join key
     "hlo":  "<compiled.as_text()>",  # optimized HLO text
     "meta": {...}}                   # the contract, see below

The ``meta`` contract (producers: ``FusedTrainStep._capture_program``;
every key optional — a missing key disables the rule that reads it):

* ``donated`` — tuple of flat entry-parameter numbers the builder
  donated (H001 requires each in the input-output alias map).
* ``plan`` — {collective kind: analytic payload bytes} for one step
  (H002; the 4-bytes-per-trainable-param gradient all-reduce model the
  BENCH_MODEL=gspmd_step gate validated at <1% wire error).
* ``replicated_slots`` — top-level output tuple indices pinned ``P()``
  (H003: loss=0, aux=4, health=5 in the GSPMD fused step).
* ``out_specs`` — per top-level output slot, the list of partition-
  spec tuples the executable actually carries (H003's measured side;
  extracted eagerly from ``compiled.output_shardings`` at capture so
  no artifact pins device state).
* ``dtype`` — dominant trainable-param dtype key (``bf16``/``f32``/
  ...); H004 activates only on declared-low-precision programs.
* ``mesh`` — axis-name -> size dict, for reports.
* ``gspmd`` — True for the one-GSPMD-program step mode.

The capture sources: :func:`from_profiler` drains the compile
registry's program store (``profiler.record_program``, fed by every
fused-step AOT compile — tier-1 dryruns make every signature
analyzable with no new lowering work), and :func:`dryrun_programs`
runs the built-in three-mesh CPU dryrun (dp8, dp4xtp2, dp2xtp2xsp2 —
the standing BENCH_MODEL=gspmd_step configs) to produce them on
demand for the CLI and the bench gate.
"""
from __future__ import annotations

import os

_COMM_MODEL_UNSET = object()
_COMM_MODEL = _COMM_MODEL_UNSET


def load_comm_model():
    """benchmark/comm_model.py as a module (it lives outside the
    package tree; same file-location import the fused step uses), or
    None when unavailable."""
    global _COMM_MODEL
    if _COMM_MODEL is _COMM_MODEL_UNSET:
        try:
            import importlib.util
            path = os.path.join(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
                "benchmark", "comm_model.py")
            spec = importlib.util.spec_from_file_location(
                "_hlolint_comm_model", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _COMM_MODEL = mod
        except Exception:
            _COMM_MODEL = None
    return _COMM_MODEL


def make_artifact(name, sig, hlo, meta=None):
    """Normalize one program into the artifact shape rules consume."""
    return {"name": str(name), "sig": str(sig), "hlo": str(hlo or ""),
            "meta": dict(meta or {})}


def from_profiler(name=None):
    """Artifacts from the profiler's program store (oldest first)."""
    from mxnet_tpu import profiler
    return [make_artifact(r["name"], r["sig"], r["hlo"], r["meta"])
            for r in profiler.program_records(name)]


# the standing mesh configs every sharded-step gate exercises
DRYRUN_MESHES = (
    ("dp8", None),                       # manual-dp shard_map mode
    ("dp4_tp2", {"dp": 4, "tp": 2}),     # GSPMD, model-parallel
    ("dp2_tp2_sp2", {"dp": 2, "tp": 2, "sp": 2}),  # 3D
)


def _dryrun_one(mesh, steps=4, seed=0):
    """One tiny fused-step training net on ``mesh`` (the
    BENCH_MODEL=gspmd_step harness): enough steps to pass warming so
    the signature compiles and the AOT capture records its program."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    rs = onp.random.RandomState(seed)
    # named here and not by the process's running count of layers: the
    # step's signature follows the parameters' sorted names, and "dense10_"
    # sorts before "dense9_"
    net = nn.HybridSequential(prefix="dryrun_")
    net.add(nn.Dense(16, activation="relu", in_units=12, prefix="dryrun_0_"))
    net.add(nn.Dense(4, in_units=16, prefix="dryrun_1_"))
    net.initialize()
    net.hybridize()
    for _, p in sorted(net.collect_params().items()):
        p.set_data(mx.nd.array(
            rs.randn(*p.shape).astype(onp.float32) * 0.1))
    loss = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    step = tr.fuse_step(lambda xx, yy: loss(net(xx), yy), mesh=mesh,
                        bucket_bytes=512)
    data = onp.random.RandomState(7)
    for _ in range(steps):
        x = mx.nd.array(data.rand(8, 12).astype(onp.float32))
        y = mx.nd.array(data.rand(8, 4).astype(onp.float32))
        step(x, y, batch_size=8)
    return step


def dryrun_programs(configs=DRYRUN_MESHES, repeat_first=False):
    """Run the built-in CPU dryrun over ``configs`` (name, axes-dict —
    None = first 8 devices, manual dp) and return the artifacts it
    captured. ``repeat_first=True`` builds the first config's step a
    second time so its signature has two lowerings and H005 checks a
    real group, not a singleton. Requires the 8-device virtual CPU
    platform (tools.launch.force_virtual_cpu_devices)."""
    from tools.launch import force_virtual_cpu_devices
    force_virtual_cpu_devices(8)
    import jax
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import create_mesh

    # Select "captured after this point" by the store's monotonic seq,
    # not a list index — the _PROGRAM_CAP ring trims the front, so an
    # index snapshot goes stale whenever earlier runs filled the store.
    before_seq = max((r.get("seq", -1)
                      for r in profiler.program_records()), default=-1)
    for i, (name_, axes) in enumerate(configs):
        mesh = create_mesh(devices=jax.devices()[:8]) if axes is None \
            else create_mesh(**axes)
        _dryrun_one(mesh)
        if repeat_first and i == 0:
            _dryrun_one(mesh)
    return [make_artifact(r["name"], r["sig"], r["hlo"], r["meta"])
            for r in profiler.program_records()
            if r.get("seq", -1) > before_seq]

"""On-device numerics sweep (VERDICT r3 item 8).

The CPU test suite never touches the real chip; this harness runs N
representative ops per family on the attached device and compares
against goldens from a CPU run of the SAME op set (deterministic
inputs), the analog of the reference's `check_consistency` GPU suite
(ref: tests/python/gpu/test_operator_gpu.py). Mosaic/XLA:TPU numeric
drift shows up here as per-op max-ulp / max-abs error.

Two modes (same file, different backends):
    JAX_PLATFORMS=cpu python benchmark/tpu_numerics.py --golden g.npz
    python benchmark/tpu_numerics.py --check g.npz   # on the device
(--golden stamps the producing platform into the npz and --check
refuses a non-cpu golden: a device-made golden would diff to 0.)

bench.py runs both automatically under BENCH_NUMERICS=1 (golden in a
CPU subprocess) and embeds the result in the bench JSON. The flash
attention kernels (fwd + bwd, NON-interpret) are additionally checked
in-process against the f32 jnp reference attention.
"""
import argparse
import json
import subprocess
import sys
import os

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _inputs(op, rs):
    """Deterministic representative inputs per op (identical across the
    golden and check processes)."""
    f = lambda *s: rs.rand(*s).astype("float32")  # noqa: E731
    return {
        # elemwise / transcendental
        "exp": [f(64, 64) * 4 - 2], "log": [f(64, 64) + 0.1],
        "tanh": [f(64, 64) * 6 - 3], "sigmoid": [f(64, 64) * 8 - 4],
        "erf": [f(64, 64) * 4 - 2], "rsqrt": [f(64, 64) + 0.05],
        # reductions
        "sum": [f(32, 128)], "mean": [f(32, 128)], "max": [f(32, 128)],
        "norm": [f(32, 128)],
        # linalg / matmul
        "dot": [f(96, 64), f(64, 80)],
        "linalg_gemm2": [f(32, 48), f(48, 24)],
        "linalg_potrf": [None],  # built below (SPD)
        "FullyConnected": [f(32, 64), f(16, 64), f(16)],
        # nn
        "Convolution": [f(4, 8, 16, 16), f(12, 8, 3, 3), f(12)],
        "BatchNorm": [f(8, 16, 8, 8), f(16), f(16), f(16), f(16)],
        "Pooling": [f(4, 8, 16, 16)],
        "softmax": [f(32, 100) * 10 - 5],
        "LayerNorm": [f(16, 128), f(128), f(128)],
        "log_softmax": [f(32, 100) * 10 - 5],
        # tensor manipulation
        "topk": [f(16, 200)], "sort": [f(16, 200)],
        "cumsum": [f(16, 128)],
        "take": [f(50, 8), rs.randint(0, 50, (20,)).astype("float32")],
    }[op]


def _call(op, ins):
    from mxnet_tpu.ops import registry
    import jax

    kwargs = {
        "sum": {"axis": 1}, "mean": {"axis": 1}, "max": {"axis": 1},
        "norm": {"ord": 2, "axis": 1},
        "FullyConnected": {"num_hidden": 16},
        "Convolution": {"kernel": (3, 3), "num_filter": 12,
                        "pad": (1, 1)},
        "BatchNorm": {"eps": 1e-3, "fix_gamma": False,
                      "_training": True},
        "Pooling": {"kernel": (2, 2), "stride": (2, 2),
                    "pool_type": "max"},
        "topk": {"k": 5, "ret_typ": "value"},
        "cumsum": {"axis": 1},
        "take": {"axis": 0},
    }.get(op, {})
    fn = registry.get_op(op).fn
    out = jax.jit(lambda *a: fn(*a, **kwargs))(*ins)
    if isinstance(out, (tuple, list)):
        out = out[0]
    return np.asarray(jax.block_until_ready(out))


OPS = ["exp", "log", "tanh", "sigmoid", "erf", "rsqrt",
       "sum", "mean", "max", "norm",
       "dot", "linalg_gemm2", "linalg_potrf", "FullyConnected",
       "Convolution", "BatchNorm", "Pooling", "softmax", "LayerNorm",
       "log_softmax",
       "topk", "sort", "cumsum", "take"]

# Per-op max-ULP budgets (VERDICT r4 item 3: "a sweep without a gate will
# silently absorb regressions"). Set at ~4x the worst value measured on
# the real chip in r4 (BENCH_r04.json per_op) so legitimate backend drift
# fits but an order-of-magnitude regression fails the sweep, bench, and
# CI. The matmul family at DEFAULT precision measures the documented
# bf16-multiply MXU policy (mxnet_tpu/precision.py), hence the loose
# 80k budgets there; the two precision-control entries prove the
# float32/highest escape hatches stay tight.
ULP_BUDGETS = {
    # log/tanh dropped 16384/8192 -> 256 in PR 9: ops/elemwise.py now
    # routes log through an exponent-split + log1p form (1 ULP vs f64
    # truth on CPU) and tanh through an expm1 form (4 ULP), so the
    # gate ENFORCES the campaign target instead of reporting the raw
    # TPU polynomial drift (was 3,396 / 1,267 measured in r05).
    "exp": 256, "log": 256, "tanh": 256, "sigmoid": 512, "erf": 64,
    "rsqrt": 32,
    "sum": 32, "mean": 32, "max": 8, "norm": 32,
    "dot": 80000, "linalg_gemm2": 80000, "linalg_potrf": 4096,
    "FullyConnected": 80000, "Convolution": 80000,
    # BatchNorm 50000 -> 64: batch_moments pins the mean to a
    # deterministic pairwise tree (bitwise equal across backends), so
    # the x-mean cancellation no longer amplifies reduction-order
    # noise; what remains is var last-bit noise through 1/sqrt
    "BatchNorm": 64, "Pooling": 8, "softmax": 512, "LayerNorm": 4096,
    "log_softmax": 4096,
    "topk": 8, "sort": 8, "cumsum": 64, "take": 8,
    "dot_precision_highest": 16,
    "dot_policy_float32": 16,
}
MODEL_REL_ERR_BUDGET = 0.02      # r4 measured 0.0045 (f32 conv decomp)
FLASH_FWD_REL_BUDGET = 1e-3      # r4 measured 1.07e-4
FLASH_BWD_ABS_BUDGET = 2e-2     # r4 measured 4.2e-3


def apply_gate(out):
    """Check the sweep result against the budgets; returns the list of
    breach strings and stamps out["gate"]."""
    breaches = []
    for op, rec in out["per_op"].items():
        budget = ULP_BUDGETS.get(op)
        if budget is not None and rec["max_ulp"] > budget:
            breaches.append("%s: %d ULP > budget %d"
                            % (op, rec["max_ulp"], budget))
    rel = out.get("model_resnet18_rel_err")
    if rel is not None and rel > MODEL_REL_ERR_BUDGET:
        breaches.append("model_resnet18_rel_err: %g > %g"
                        % (rel, MODEL_REL_ERR_BUDGET))
    if out["flash_fwd_rel_err"] > FLASH_FWD_REL_BUDGET:
        breaches.append("flash_fwd_rel_err: %g > %g"
                        % (out["flash_fwd_rel_err"], FLASH_FWD_REL_BUDGET))
    if out["flash_bwd_max_abs_err"] > FLASH_BWD_ABS_BUDGET:
        breaches.append("flash_bwd_max_abs_err: %g > %g"
                        % (out["flash_bwd_max_abs_err"],
                           FLASH_BWD_ABS_BUDGET))
    out["gate"] = {"ok": not breaches, "breaches": breaches}
    return breaches


def run_ops():
    results = {}
    import zlib
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.precision import matmul_precision
    from mxnet_tpu.ops import registry
    # The whole sweep is PINNED to the default policy: the budgets and the
    # module comments calibrate the DEFAULT bf16 MXU path, and an exported
    # MXTPU_MATMUL_PRECISION (applied globally at mxnet_tpu import) must
    # not silently shift what the per_op table measures. The two precision
    # controls below override locally, inside the pin.
    with matmul_precision("default"):
        rs = np.random.RandomState(42)
        a = rs.rand(96, 64).astype("float32")
        b = rs.rand(64, 80).astype("float32")
        # control: the matmul-family ULP gap is the TPU's default
        # bf16-multiply matmul policy, not a kernel bug — HIGHEST-precision
        # dot must collapse it by orders of magnitude
        hi = jax.jit(lambda x, y: jnp.dot(x, y, precision="highest"))
        results["dot_precision_highest"] = np.asarray(
            jax.block_until_ready(hi(a, b)))
        # second control THROUGH the repo's own op layer: the registry
        # `dot` under the float32 policy context (mxnet_tpu/precision.py)
        # must land within a few ULP of the CPU golden — proves the
        # user-facing knob, not just raw jnp, defeats the bf16 default
        with matmul_precision("float32"):
            out = jax.jit(registry.get_op("dot").fn)(a, b)
            results["dot_policy_float32"] = np.asarray(
                jax.block_until_ready(out))
        for op in OPS:
            # crc32, NOT hash(): str hashing is salted per process and the
            # golden/check runs live in different processes
            rs = np.random.RandomState(zlib.crc32(op.encode()) % (2 ** 31))
            if op == "linalg_potrf":
                a = rs.rand(24, 24).astype("float32")
                ins = [a @ a.T + 24 * np.eye(24, dtype="float32")]
            else:
                ins = _inputs(op, rs)
            results[op] = _call(op, ins)
    return results


def _max_ulp(a, b):
    """Max ULP distance between two same-shape f32 arrays (bit distance
    of the IEEE totally-ordered representation)."""
    ai = a.astype(np.float32).view(np.int32).astype(np.int64)
    bi = b.astype(np.float32).view(np.int32).astype(np.int64)
    # map negative floats onto the descending side of the number line
    ai = np.where(ai < 0, np.int64(-2147483648) - ai, ai)
    bi = np.where(bi < 0, np.int64(-2147483648) - bi, bi)
    return int(np.max(np.abs(ai - bi))) if a.size else 0


def check_flash():
    """Flash fwd+bwd (non-interpret when on TPU) vs jnp reference
    attention, both evaluated on THIS device in f32."""
    import importlib

    import jax
    import jax.numpy as jnp

    # the package __init__ re-exports the flash_attention FUNCTION under
    # the module's name; load the module itself
    FA = importlib.import_module(
        "mxnet_tpu.pallas_kernels.flash_attention")

    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.rand(2, 4, 256, 64).astype("float32") - 0.5)
    k = jnp.asarray(rs.rand(2, 4, 256, 64).astype("float32") - 0.5)
    v = jnp.asarray(rs.rand(2, 4, 256, 64).astype("float32") - 0.5)

    def loss_flash(q, k, v):
        return jnp.sum(FA.flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(FA.attention_reference(q, k, v, causal=True) ** 2)

    of, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    orf, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    fwd_err = float(abs(np.asarray(of) - np.asarray(orf))
                    / max(abs(float(orf)), 1e-9))
    bwd_err = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(gf, gr))
    return {"flash_fwd_rel_err": round(fwd_err, 9),
            "flash_bwd_max_abs_err": round(bwd_err, 9),
            "pallas_active": bool(FA._use_pallas())}


def run_model():
    """Deterministic whole-model forward — the model-level analog of the
    op sweep (ref pattern: tests/python/gpu/test_operator_gpu.py runs
    full models on the device too). A thumbnail ResNet-18 eval forward
    exercises layout choices, conv/BN/pool fusion decisions, and the
    Gluon->jit tracing path that per-op checks cannot see."""
    import mxnet_tpu as mx
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    import random as _pyrandom
    # deterministic init WITHOUT leaking reseeded global streams into
    # whatever runs after (bench.py calls this mid-process)
    py_state = _pyrandom.getstate()
    np_state = np.random.get_state()
    mx_state = (mxrandom._STATE.seed, mxrandom._STATE.counter,
                mxrandom._STATE.base_key, mxrandom._HOST_RNG.get_state())
    try:
        _pyrandom.seed(0)
        np.random.seed(0)
        mx.random.seed(0)
        from mxnet_tpu import autograd

        net = resnet18_v1(thumbnail=True)
        net.initialize()
        rs = np.random.RandomState(11)
        x = mx.nd.array(rs.rand(4, 3, 32, 32).astype("float32"))
        with autograd.pause():
            net(x)  # finish deferred init (host)
        # eager NDArrays are host-committed (default ctx cpu) and ops
        # follow operand placement — without explicit placement the
        # "device" check would silently run on the host CPU and match
        # the golden bit-exactly, checking nothing. reset_ctx /
        # as_in_context keep each array's .context consistent with the
        # buffer (Context('tpu') falls back to host on cpu-only runs,
        # preserving the golden process's behavior).
        tpu = mx.context.Context("tpu")
        net.collect_params().reset_ctx(tpu)
        x = x.as_in_context(tpu)
        with autograd.pause():
            out = net(x)
        return np.asarray(out.asnumpy())
    finally:
        _pyrandom.setstate(py_state)
        np.random.set_state(np_state)
        (mxrandom._STATE.seed, mxrandom._STATE.counter,
         mxrandom._STATE.base_key) = mx_state[:3]
        mxrandom._HOST_RNG.set_state(mx_state[3])


def sweep(golden_path):
    import jax
    golden = np.load(golden_path)
    # a golden accidentally produced on an accelerator would make
    # every device-vs-golden diff read 0 — refuse it
    gplat = (str(golden["__platform__"]) if "__platform__" in golden
             else "<unstamped>")
    if gplat != "cpu":
        raise RuntimeError(
            "golden %s was produced on %r, not cpu — rerun --golden "
            "under JAX_PLATFORMS=cpu" % (golden_path, gplat))
    mine = run_ops()
    per_op = {}
    worst = None
    for op in OPS + ["dot_precision_highest", "dot_policy_float32"]:
        if op not in golden.files:  # golden from an older harness rev
            continue
        g = golden[op]
        m = mine[op]
        ulp = _max_ulp(m, g)
        per_op[op] = {"max_ulp": ulp,
                      "max_abs": float(np.max(np.abs(m - g)))
                      if g.size else 0.0}
        if worst is None or ulp > worst[1]:
            worst = (op, ulp)
    out = {
        "platform": jax.devices()[0].platform,
        "n_ops": len(OPS),
        "worst_op": worst[0],
        "worst_ulp": worst[1],
        "per_op": per_op,
    }
    if "__model__" in golden:
        m = run_model()
        g = golden["__model__"]
        # ULP distance is meaningless for near-zero logits (a sign flip
        # at 1e-8 is ~2^31 ULP), so the headline is max_abs relative to
        # the output scale; TPU f32 convs legitimately differ from CPU
        # (bf16-passes decomposition) and this is where that shows up
        max_abs = float(np.max(np.abs(m - g)))
        out["model_resnet18_max_abs"] = max_abs
        out["model_resnet18_rel_err"] = float(
            max_abs / (np.max(np.abs(g)) + 1e-12))
    out.update(check_flash())
    apply_gate(out)
    return out


def run_with_cpu_golden():
    """bench.py hook: golden in a CPU subprocess, check on this device."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        gpath = os.path.join(td, "golden.npz")
        # the parent holds the chip: the golden child is pinned to the
        # CPU backend, the only one it may touch
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        try:
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--golden",
                 gpath],
                env=env, check=True, capture_output=True, timeout=900)
        except subprocess.CalledProcessError as e:
            # surface the child's traceback — CalledProcessError's own
            # message drops the captured stderr
            tail = (e.stderr or b"").decode("utf-8", "replace")[-800:]
            raise RuntimeError(
                "golden subprocess failed (exit %d): %s"
                % (e.returncode, tail)) from e
        return sweep(gpath)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--golden", default=None)
    ap.add_argument("--check", default=None)
    args = ap.parse_args()
    if args.golden:
        import jax
        platform = jax.devices()[0].platform
        np.savez(args.golden, __platform__=np.array(platform),
                 __model__=run_model(),
                 **run_ops())
        print("wrote %s (%d ops, %s)" % (args.golden, len(OPS),
                                         platform))
        return
    out = sweep(args.check) if args.check else run_with_cpu_golden()
    print(json.dumps(out, indent=1))
    if not out["gate"]["ok"]:
        # the gate is the point of the sweep — a breach is a FAILURE,
        # not a statistic (VERDICT r4 weak #3)
        sys.exit("ULP gate breached: %s" % "; ".join(
            out["gate"]["breaches"]))


if __name__ == "__main__":
    main()

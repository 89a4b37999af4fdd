"""Communication decomposition + scaling projection (VERDICT r4 #2).

The r1-r4 SCALING_r*.json measured multiprocess wall-clock on a ONE-core
CI host, where n ranks timeshare one core — an efficiency number that
says nothing about hardware scaling. This harness replaces it with what
IS measurable here, plus a clearly-labeled model for what is not:

1. MEASURED (virtual 8-device mesh, compiled HLO): per-step collective
   payload bytes by kind (all-reduce / all-gather / reduce-scatter /
   collective-permute / all-to-all) and per-step FLOPs, for three
   sharded train-step configs (pure dp, dp x tp, dp x tp x sp). These
   come from the SPMD partitioner's actual output, not hand counting.
2. VALIDATED: the analytic gradient-all-reduce payload (4 bytes/param)
   is checked against the HLO measurement on the pure-dp config; the
   model is only trusted because this delta is small.
3. PROJECTED: ring-all-reduce step efficiency at n = 8..256 chips for
   the two real single-chip workloads whose step times were measured on
   the attached v5e (bench.py), under stated ICI/DCN bandwidth
   assumptions — against the reference's published 90.1% at 256 GPUs
   (ref: example/image-classification/README.md:309-319).

    python benchmark/comm_model.py --out SCALING_r05.json
(CPU env: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8)
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

# one HLO result type, e.g. f32[512,128]{1,0} or bf16[] or (…, …)
_SHAPE_RE = re.compile(r"(%s)\[([\d,]*)\]" % "|".join(_DTYPE_BYTES))


def _shape_bytes(type_str):
    """Total bytes of an HLO result type (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _tuple_elements(type_str):
    """Top-level elements of a tuple type ``(a, b, ...)``; [] when the
    type is not a tuple. Layout braces (``{1,0}``) nest commas, so the
    split tracks depth across (), [] and {}."""
    s = type_str.strip()
    if not s.startswith("("):
        return []
    depth, start, elems = 0, 1, []
    for i, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                elems.append(s[start:i])
                break
        elif ch == "," and depth == 1:
            elems.append(s[start:i])
            start = i + 1
    return elems


def _split_computations(hlo_text):
    """{computation_name: [lines]} for every computation block."""
    comps = {}
    name, buf, depth = None, [], 0
    for line in hlo_text.splitlines():
        if name is None:
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+)(?:\.clone)? \(.*\{",
                         line)
            if m:
                name = m.group(1)
                buf = [line]
                depth = line.count("{") - line.count("}")
                if depth <= 0:
                    comps[name] = buf
                    name = None
            continue
        buf.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            comps[name] = buf
            name = None
    return comps


def _trip_count(cond_lines):
    """Trip count of a canonical jax-scan while loop. The bound is the
    scalar integer constant the condition compares the induction
    variable against; post-optimization the compare itself often hides
    inside a wrapped_compare fusion, so: exactly one scalar int
    constant in the condition computation => that is the bound. None
    when the bound is loop-carried (caller falls back)."""
    consts = [int(m.group(1)) for line in cond_lines
              for m in [re.search(
                  r"= [su]\d+\[\] constant\((\d+)\)", line)] if m]
    return consts[0] if len(consts) == 1 else None


def _is_degenerate_groups(line):
    """True when the collective's replica_groups are singletons — a
    one-member group exchanges nothing, so the op is sharding
    bookkeeping, not wire traffic (r07 fix: the shard_map'd loss emits
    one such no-op AR per layer-stack leaf, which inflated the measured
    payload by a full parameter's worth of phantom bytes)."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[", line)
    if m:
        return int(m.group(2)) == 1   # iota form: [groups, per_group]
    m = re.search(r"replica_groups=\{(\{[^}]*\})", line)
    if m:
        return "," not in m.group(1)  # literal form: first group {n}
    return False


def hlo_collective_bytes(hlo_text):
    """Per-kind collective payload bytes for ONE step, loop-aware: a
    collective inside a `while` body (jax.lax.scan over layers / loss
    chunks) executes trip-count times, so body bytes are multiplied by
    the trip count parsed from the loop condition (r5 fix: the static
    count under-reported by exactly (L-1) layers' gradients).
    Degenerate collectives (singleton replica groups) are skipped —
    they move zero bytes.

    Returns (bytes_by_kind, counts_by_kind, n_unresolved_loops)."""
    comps = _split_computations(hlo_text)
    coll_re = re.compile(r"=\s+(\(.*?\)|\S+)\s+(%s)(-start)?\("
                         % "|".join(_COLLECTIVES))
    while_re = re.compile(
        r"while\(.*condition=%([\w.\-]+), body=%([\w.\-]+)")
    unresolved = [0]

    def bytes_of(comp_name, seen):
        out = {k: 0 for k in _COLLECTIVES}
        counts = {k: 0 for k in _COLLECTIVES}
        if comp_name not in comps or comp_name in seen:
            return out, counts
        for line in comps[comp_name]:
            m = coll_re.search(line)
            if m and "-done" not in line.split("=", 1)[1][:60] \
                    and not _is_degenerate_groups(line):
                ty = m.group(1)
                if m.group(3):
                    # async form: the -start result type is a tuple of
                    # (operand, result[, context...]) — e.g. a
                    # collective-permute-start carries two trailing
                    # u32[] context elements. Summing the whole tuple
                    # double-counts the payload, so keep only the
                    # result element, always the second (the -done
                    # side is already skipped)
                    elems = _tuple_elements(ty)
                    if len(elems) >= 2:
                        ty = elems[1]
                out[m.group(2)] += _shape_bytes(ty)
                counts[m.group(2)] += 1
            w = while_re.search(line)
            if w:
                cond, body = w.groups()
                trips = _trip_count(comps.get(cond, []))
                sub, subc = bytes_of(body, seen | {comp_name})
                if any(sub.values()) and trips is None:
                    unresolved[0] += 1
                    trips = 1
                for k in _COLLECTIVES:
                    out[k] += (trips or 1) * sub[k]
                    counts[k] += (trips or 1) * subc[k]
        return out, counts

    entry = None
    for line in hlo_text.splitlines():
        m = re.match(r"ENTRY %?([\w.\-]+)", line)
        if m:
            entry = m.group(1)
            break
    out, counts = bytes_of(entry, frozenset())
    return out, counts, unresolved[0]


def collect_hlo_inventory(program):
    """The one choke point for compiled-program collective inventory:
    accepts a compiled executable (anything with ``as_text()``) or raw
    HLO text and returns the per-kind payload decomposition every
    consumer reads the same way — bench gates, the fused-step compile
    attribution, and hlolint H002 (which diffs it against the analytic
    plan). Returns ``{"bytes_by_kind", "counts_by_kind",
    "unresolved_loops", "total_bytes"}``."""
    txt = program if isinstance(program, str) \
        else program.as_text()
    by_kind, counts, unresolved = hlo_collective_bytes(txt or "")
    return {
        "bytes_by_kind": by_kind,
        "counts_by_kind": counts,
        "unresolved_loops": unresolved,
        "total_bytes": sum(by_kind.values()),
    }


def measure_config(name, mesh_axes, cfg_kwargs, B, S):
    """Compile one sharded train step on the virtual mesh; return the
    collective decomposition + cost-analysis FLOPs."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel import transformer as T

    mesh = create_mesh(devices=jax.devices()[:8], **mesh_axes)
    cfg = T.TransformerConfig(**cfg_kwargs)
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        toks = jnp.zeros((B, S), jnp.int32)
        compiled = step_fn.lower(state, toks, toks).compile()
    inv = collect_hlo_inventory(compiled)
    by_kind, counts, unresolved = (inv["bytes_by_kind"],
                                   inv["counts_by_kind"],
                                   inv["unresolved_loops"])
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    n_params = sum(int(jnp.size(p))
                   for p in jax.tree_util.tree_leaves(state[0]))
    return {
        "config": name,
        "mesh": mesh_axes,
        "params": n_params,
        "batch": B, "seq": S,
        "flops_per_step": float(cost.get("flops", 0.0)) if cost else None,
        "collective_payload_bytes": by_kind,
        "collective_counts": counts,
        "unresolved_loops": unresolved,
    }


# -- the projection model ---------------------------------------------------

# Public per-chip numbers for TPU v5e, stated as model ASSUMPTIONS
# (zero-egress environment; values from the public v5e datasheet and
# the jax-ml scaling book): bf16 peak 197 TF/s; 4 ICI links/chip at
# ~45 GB/s each way -> ~180 GB/s aggregate per chip; DCN ~25 GB/s per
# 8-chip host. Ring all-reduce moves 2(n-1)/n x payload per chip.
ASSUMPTIONS = {
    "chip": "TPU v5e",
    # jax.devices()[0].device_kind as that chip reports it (chip run,
    # PR 21); the rates below describe this kind and no other
    "device_kind": "TPU v5 lite",
    "bf16_peak_tflops": 197.0,
    # Peak matmul throughput by dominant program dtype. bf16 is the
    # datasheet number; f32 runs the MXU at half rate; int8 doubles it
    # (the PR 9 quantized_matmul path is what actually hits this peak —
    # its epilogue-fused dequant keeps the 2x from being eaten by
    # casts). f16 aliases bf16 (same MXU rate on this part).
    "peak_tflops": {
        "bf16": 197.0,
        "f16": 197.0,
        "f32": 98.5,
        "int8": 394.0,
    },
    "hbm_bw_GBps": 819.0,
    "ici_bw_per_chip_GBps": 180.0,
    "dcn_bw_per_host_GBps": 25.0,
    "chips_per_host": 8,
    "allreduce_algorithm": "ring, wire bytes = 2(n-1)/n * payload",
    "overlap": "both bounds reported: none (serial) and full "
               "(comm hidden under compute)",
}


def modeled_assumptions():
    """ASSUMPTIONS, after checking that they describe the attached
    device. On the CPU backend the table is a stated model of a v5e
    (the CPU contract gates price against it); on any other backend a
    ``device_kind`` the table does not name is an error — an MFU or a
    roofline share against another chip's peak is a wrong number, not
    an approximate one."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "cpu" \
            and dev.device_kind != ASSUMPTIONS["device_kind"]:
        raise ValueError(
            "comm_model.ASSUMPTIONS holds the rates of %r; the attached "
            "device is %r (%s) and has no entry"
            % (ASSUMPTIONS["device_kind"], dev.device_kind,
               dev.platform))
    return ASSUMPTIONS


def peak_tflops(dtype="bf16"):
    """Peak TFLOP/s of the attached device for a program whose dominant
    dtype is ``dtype`` (a short key: ``bf16``/``f16``/``f32``/``int8``).
    Unknown dtypes fall back to the bf16 peak — the conservative
    default the modeled compute time has always used; an unknown
    DEVICE raises (``modeled_assumptions``)."""
    table = modeled_assumptions()["peak_tflops"]
    return table.get(str(dtype), table["bf16"])


def allreduce_seconds(payload_bytes, n):
    """(t_ici, t_dcn) seconds to ring-all-reduce one payload at n
    chips under ASSUMPTIONS: 2(n-1)/n x payload over per-chip ICI, plus
    the hierarchical DCN term for multi-host (payload re-reduced across
    hosts at host DCN bandwidth). The single place the wire-time
    formula lives — `project` and bench.py's comm_overlap gate both
    price collectives through it."""
    ici = ASSUMPTIONS["ici_bw_per_chip_GBps"] * 1e9
    dcn = ASSUMPTIONS["dcn_bw_per_host_GBps"] * 1e9
    per_host = ASSUMPTIONS["chips_per_host"]
    t_ici = 2.0 * (n - 1) / n * payload_bytes / ici
    hosts = max(1, n // per_host)
    t_dcn = (2.0 * (hosts - 1) / hosts * payload_bytes / dcn
             if hosts > 1 else 0.0)
    return t_ici, t_dcn


def project(step_time_s, grad_payload_bytes, ns):
    """Ring-all-reduce efficiency at n chips over ICI, plus the
    hierarchical DCN term for multi-host (payload re-reduced across
    hosts at host DCN bandwidth)."""
    rows = []
    for n in ns:
        t_ici, t_dcn = allreduce_seconds(grad_payload_bytes, n)
        t_comm = t_ici + t_dcn
        rows.append({
            "n": n,
            "comm_ms": round(t_comm * 1e3, 2),
            "ici_ms": round(t_ici * 1e3, 2),
            "dcn_ms": round(t_dcn * 1e3, 2),
            "efficiency_no_overlap": round(
                step_time_s / (step_time_s + t_comm), 4),
            "efficiency_full_overlap": round(
                min(1.0, step_time_s / max(step_time_s, t_comm)), 4),
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    V, D, L = 512, 128, 2
    small = dict(vocab_size=V, dim=D, n_layers=L, n_heads=4,
                 ffn_hidden=4 * D, attn_mode="local", loss_chunks=4)
    measured = [
        measure_config("pure_dp", {"dp": 8}, small, B=16, S=64),
        measure_config("dp_x_tp", {"dp": 4, "tp": 2}, small, B=16, S=64),
        measure_config("dp_tp_sp", {"dp": 2, "tp": 2, "sp": 2},
                       dict(small, attn_mode="ring"), B=16, S=64),
    ]

    # validation: pure-dp grad all-reduce payload vs the analytic
    # model. The naive 4-bytes/param model is WRONG in an instructive
    # way the HLO exposed: the chunked-CE scan all-reduces the
    # unembedding gradient once PER CHUNK (XLA keeps the AR inside the
    # loop), so dynamic payload = params + (chunks-1) * vocab*dim
    # (+ the scalar loss). This decomposition reproduces the measured
    # bytes exactly and is itself the r5 finding: chunked CE trades
    # HBM for (loss_chunks-1) extra unembedding-grad reductions.
    dp = measured[0]
    chunks, vocab, dim = small["loss_chunks"], small["vocab_size"], \
        small["dim"]
    analytic = 4 * (dp["params"] + (chunks - 1) * vocab * dim + 1)
    got = dp["collective_payload_bytes"]["all-reduce"]
    delta = abs(got - analytic) / analytic
    validation = {
        "analytic_model": "4B * (params + (loss_chunks-1)*vocab*dim "
                          "+ loss_scalar)",
        "analytic_grad_allreduce_bytes": analytic,
        "hlo_measured_allreduce_bytes": got,
        "rel_delta": round(delta, 6),
        "model_trusted": bool(delta < 0.05),
        "naive_4B_per_param_bytes": 4 * dp["params"],
        "finding": (
            "chunked-CE re-all-reduces the unembedding grad per chunk "
            "(+(chunks-1)*vocab*dim*4 bytes/step). Root cause isolated "
            "(r5): GSPMD keeps the AR inside ANY scan that accumulates "
            "a batch-sharded contraction — scan carries must hold a "
            "concrete sharding, so each iteration's partial sum is "
            "reduced before the add; reproduced with a 10-line minimal "
            "scan, and a hand-written custom-vjp accumulation compiles "
            "to the same HLO. Fixing it needs Explicit-mode "
            "PartitionSpec(unreduced=...) shardings (rejected: "
            "framework-wide mesh-mode migration) or a shard_map'd loss "
            "mirroring every dp x tp x sp layout by hand. Documented "
            "cost, not a bug: single-chip perf is unaffected."),
    }

    # projections for the two REAL single-chip workloads (step times
    # measured on the attached v5e by bench.py; BENCH_r04/r05). The
    # transformer is projected under BOTH gradient-payload patterns:
    # the observed XLA lowering (chunked CE re-reduces the 131M-param
    # unembedding grad each of the 8 chunks) and the ideal
    # one-AR-per-param pattern the finding above would restore.
    ns = [8, 16, 32, 64, 128, 256]
    t_params = 1_604_400_000
    t_unembed = 32000 * 4096
    t_ideal = 4 * t_params
    t_observed = 4 * (t_params + 7 * t_unembed)
    projections = {
        "resnet50_b128_bf16": {
            "measured_step_s": 0.0495,  # 2586 img/s at b128 (BENCH_r04)
            "grad_payload_bytes": 4 * 25_557_032,
            "rows": project(0.0495, 4 * 25_557_032, ns),
        },
        "transformer_1p6B_b12_s2048": {
            "measured_step_s": 1.909,  # 12,869 tok/s at b12 x s2048
            "grad_payload_bytes": t_ideal,
            "rows": project(1.909, t_ideal, ns),
        },
        "transformer_1p6B_b12_s2048_observed_chunked_ce": {
            "measured_step_s": 1.909,
            "grad_payload_bytes": t_observed,
            "rows": project(1.909, t_observed, ns),
        },
    }

    out = {
        "metric": "comm_decomposition_scaling_model",
        "platform": "virtual 8-device cpu mesh (HLO measurement) + "
                    "one real v5e (step times)",
        "measured": measured,
        "validation": validation,
        "assumptions": ASSUMPTIONS,
        "projection": projections,
        "reference_bar": {
            "n": 256, "efficiency": 0.901,
            "source": "ref example/image-classification/README.md:309 "
                      "(dist_sync, 256 GPUs)",
        },
        "conclusion": (
            "At 256 v5e chips the ResNet-50 grad all-reduce costs "
            "9.1ms (1.1ms ICI + 7.9ms cross-host DCN) against a "
            "49.5ms measured step: 84.5% efficiency with ZERO "
            "overlap, ~100% once the reduction overlaps the backward "
            "pass (standard, and what the reference's own 90.1% "
            "already assumes) — DCN, not ICI, is the binding term. "
            "The transformer's exposure is larger (6.4GB f32 grads) "
            "but still fully hideable under its 1.9s step. The "
            "measurable risk is the chunked-CE AR-per-chunk pattern "
            "(validation.finding): at 256 chips it adds 36% to the "
            "transformer wire bytes unless the unembedding grad is "
            "accumulated locally first."),
    }
    js = json.dumps(out)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js + "\n")
    return out


if __name__ == "__main__":
    main()

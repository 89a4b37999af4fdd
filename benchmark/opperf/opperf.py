#!/usr/bin/env python
"""Operator micro-benchmark harness (ref: benchmark/opperf/opperf.py).

Times forward and backward of registered ops on the attached device with
warmup + repeated runs, like the reference's profiler-driven op benchmark.
Usage:
    python benchmark/opperf/opperf.py                  # default op set
    python benchmark/opperf/opperf.py --ops add,dot    # subset
    python benchmark/opperf/opperf.py --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def _rand(shape, dtype="float32", seed=0):
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    return mx.nd.array(rng.uniform(0.5, 1.5, shape).astype(dtype))


def default_specs():
    """Representative op set with benchmark shapes (mirrors the
    reference's per-category default inputs, opperf/rules/default_params.py)."""
    L = (1024, 1024)
    return {
        # unary elementwise
        "exp": lambda: ([_rand(L)], {}),
        "log": lambda: ([_rand(L)], {}),
        "sqrt": lambda: ([_rand(L)], {}),
        "tanh": lambda: ([_rand(L)], {}),
        "sigmoid": lambda: ([_rand(L)], {}),
        "relu": lambda: ([_rand(L)], {}),
        "erf": lambda: ([_rand(L)], {}),
        # binary / broadcast
        "add": lambda: ([_rand(L), _rand(L, seed=1)], {}),
        "multiply": lambda: ([_rand(L), _rand(L, seed=1)], {}),
        "broadcast_add": lambda: ([_rand(L), _rand((1024, 1), seed=1)], {}),
        "maximum": lambda: ([_rand(L), _rand(L, seed=1)], {}),
        # reductions
        "sum": lambda: ([_rand(L)], {"axis": 1}),
        "mean": lambda: ([_rand(L)], {"axis": 1}),
        "max": lambda: ([_rand(L)], {"axis": 1}),
        "argmax": lambda: ([_rand(L)], {"axis": 1}),
        "softmax": lambda: ([_rand(L)], {}),
        "log_softmax": lambda: ([_rand(L)], {}),
        # linalg / MXU
        "dot": lambda: ([_rand(L), _rand(L, seed=1)], {}),
        "batch_dot": lambda: ([_rand((32, 256, 256)),
                               _rand((32, 256, 256), seed=1)], {}),
        "FullyConnected": lambda: (
            [_rand((128, 1024)), _rand((1024, 1024), seed=1), None],
            {"num_hidden": 1024, "no_bias": True}),
        "Convolution": lambda: (
            [_rand((32, 64, 56, 56)), _rand((64, 64, 3, 3), seed=1), None],
            {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1),
             "no_bias": True}),
        # nn
        "BatchNorm": lambda: (
            [_rand((32, 64, 56, 56)), _rand((64,)), _rand((64,)),
             _rand((64,)), _rand((64,))], {}),
        "LayerNorm": lambda: (
            [_rand((128, 1024)), _rand((1024,)), _rand((1024,))], {}),
        "Pooling": lambda: (
            [_rand((32, 64, 56, 56))],
            {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}),
        # shape manipulation
        "transpose": lambda: ([_rand(L)], {}),
        "reshape": lambda: ([_rand(L)], {"shape": (512, 2048)}),
        "concat": lambda: ([_rand(L), _rand(L, seed=1)], {"dim": 1}),
        "tile": lambda: ([_rand((256, 256))], {"reps": (4, 4)}),
        # indexing
        "take": lambda: ([_rand(L),
                          _rand((1024,), "int32")], {}),
        "one_hot": lambda: ([_rand((4096,), "int32")], {"depth": 128}),
        # detection family (round 2; ref: contrib/deformable_convolution.cc,
        # psroi_pooling.cc, proposal.cc)
        "_contrib_DeformableConvolution": lambda: (
            [_rand((8, 64, 28, 28)), _rand((8, 18, 28, 28), seed=1),
             _rand((64, 64, 3, 3), seed=2)],
            {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1),
             "no_bias": True}),
        "_contrib_PSROIPooling": lambda: (
            [_rand((2, 4 * 49, 28, 28)),
             _rand_rois(16, 28)],
            {"spatial_scale": 1.0, "output_dim": 4, "pooled_size": 7,
             "group_size": 7}),
        # image family
        "_image_to_tensor": lambda: ([_rand((64, 224, 224, 3))], {}),
        "_image_resize": lambda: ([_rand((64, 224, 224, 3))],
                                  {"size": (112, 112)}),
        # quantized int8 (forward-only by nature)
        "_contrib_quantize_v2": lambda: ([_rand(L)], {}),
    }


def _rand_rois(n, size):
    import numpy as np
    rs = np.random.RandomState(7)
    x1 = rs.randint(0, size // 2, n)
    y1 = rs.randint(0, size // 2, n)
    rois = np.stack([np.zeros(n), x1, y1,
                     x1 + rs.randint(4, size // 2, n),
                     y1 + rs.randint(4, size // 2, n)], 1)
    import mxnet_tpu as mx
    return mx.nd.array(rois.astype("float32"))


def bench_op(name, make_inputs, warmup=3, runs=20, run_backward=True):
    """Time one op's forward (and backward through jax.vjp) in ms."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    args, kwargs = make_inputs()
    fn = getattr(mx.nd, name)

    def fwd():
        return fn(*args, **kwargs)

    for _ in range(max(warmup, 1)):  # >=1: the compile must not be timed
        out = fwd()
    jax.block_until_ready(out._data if hasattr(out, "_data")
                          else [o._data for o in out])
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fwd()
    jax.block_until_ready(out._data if hasattr(out, "_data")
                          else [o._data for o in out])
    fwd_ms = (time.perf_counter() - t0) / runs * 1e3

    bwd_ms = None
    if run_backward:
        diffable = [a for a in args
                    if a is not None and np.issubdtype(a.dtype, np.floating)]
        if diffable:
            for a in diffable:
                a.attach_grad()

            def loss():
                with autograd.record():
                    out = fwd()
                    head = out[0] if isinstance(out, tuple) else out
                    s = head.sum()
                s.backward()
                return diffable[0].grad
            try:
                for _ in range(max(warmup, 1)):
                    g = loss()
                jax.block_until_ready(g._data)
                t0 = time.perf_counter()
                for _ in range(runs):
                    g = loss()
                jax.block_until_ready(g._data)
                bwd_ms = (time.perf_counter() - t0) / runs * 1e3
            except Exception as e:
                print("backward failed for %s: %s" % (name, str(e)[:80]),
                      file=sys.stderr)
    return {"op": name, "fwd_ms": round(fwd_ms, 4),
            "fwd_bwd_ms": round(bwd_ms, 4) if bwd_ms is not None else None}


def run_performance_test(ops=None, warmup=3, runs=20, run_backward=True):
    """ref: opperf.py run_op_benchmarks — returns a list of result dicts."""
    specs = default_specs()
    names = ops if ops else sorted(specs)
    results = []
    for name in names:
        if name not in specs:
            print("skipping %s (no benchmark spec)" % name, file=sys.stderr)
            continue
        try:
            results.append(bench_op(name, specs[name], warmup, runs,
                                    run_backward))
        except Exception as e:
            results.append({"op": name, "error": str(e)[:120]})
    return results


# ---------------------------------------------------------------------------
# Full-registry mode: auto-generated inputs for EVERY registered op
# (ref: opperf.py runs all registered ops with inputs synthesized from
# rules/default_params.py; here inputs come from the op fn signatures)
# ---------------------------------------------------------------------------

# tensor-input shape heuristics by parameter name (small shapes: the
# full sweep must finish in CI minutes). Profiles cover the common
# rank expectations; auto_spec tries them in order until the op runs.
_B, _D = 8, 32
_SHARED_SHAPES = {
    "weight": (_D, _D), "bias": (_D,),
    "gamma": (_D,), "beta": (_D,),
    "moving_mean": (_D,), "moving_var": (_D,),
    "label": (_B,),
    "indices": (_B,), "index": (_B,),
    "grid": (2, 2, 4, 4),
    "rois": (4, 5), "anchors": (1, 16, 4), "anchor": (1, 16, 4),
    "cls_pred": (2, 2, 16), "loc_pred": (2, 64),
    "cls_prob": (2, 2, 16), "bbox_pred": (2, 64),
    "im_info": (2, 3),
    "parameters": (4096,), "state": (1, _B, _D), "state_cell": (1, _B, _D),
    "A": (2, 8, 8), "B": (2, 8, 8), "C": (2, 8, 8),
    "pred": (10, 4, 8),                      # CTC: (seq, batch, alphabet)
    "sequence_length": (_B,), "lengths": (_B,), "len_arr": (_B,),
    "min_data": (1,), "max_data": (1,),
    "min_range": (1,), "max_range": (1,),
    "min_calib": (1,), "max_calib": (1,),
    "offset": (2, 18, 8, 8),                 # deformable conv offsets
    "mask": (2, 9, 8, 8),
}
_PROFILES = (
    # rank-2 activations (the default)
    {"data": (_B, _D), "x": (_B, _D), "a": (_B, _D), "b": (_B, _D),
     "lhs": (_B, _D), "rhs": (_B, _D), "data1": (_B, _D),
     "data2": (_B, _D), "shape_like": (_B, _D), "like": (_B, _D),
     "condition": (_B, _D), "mu": (_B, _D), "sigma": (_B, _D),
     "low": (_B, _D), "high": (_B, _D), "lam": (_B, _D),
     "alpha": (_B, _D), "loc": (_B, _D), "scale": (_B, _D)},
    # rank-4 NCHW (conv/pool/spatial families)
    {"data": (2, 4, 8, 8), "x": (2, 4, 8, 8), "a": (2, 4, 8, 8),
     "b": (2, 4, 8, 8), "lhs": (2, 4, 8, 8), "rhs": (2, 4, 8, 8),
     "data1": (2, 4, 8, 8), "data2": (2, 4, 8, 8),
     "shape_like": (2, 4, 8, 8), "like": (2, 4, 8, 8),
     "condition": (2, 4, 8, 8), "weight": (8, 4, 3, 3)},
    # rank-3 (sequence/batched-matmul families)
    {"data": (2, _B, _D), "x": (2, _B, _D), "a": (2, 8, 8),
     "b": (2, 8, 8), "lhs": (2, 8, 8), "rhs": (2, 8, 8),
     "data1": (2, _B, _D), "data2": (2, _B, _D),
     "shape_like": (2, _B, _D), "like": (2, _B, _D)},
    # square rank-2 (dot/linalg/contract families)
    {"data": (_D, _D), "x": (_D, _D), "a": (_D, _D), "b": (_D, _D),
     "lhs": (_D, _D), "rhs": (_D, _D), "data1": (_D, _D),
     "data2": (_D, _D)},
    # rank-3 HWC (host image ops)
    {"data": (16, 16, 3), "x": (16, 16, 3)},
)
_INT_TENSORS = {"indices", "index", "label"}


def _mk(name, shape, dtype="float32", lo=0.5, hi=1.5, seed=0):
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    if dtype.startswith("int"):
        return mx.nd.array(rng.randint(int(lo), int(hi), shape)
                           .astype(dtype))
    return mx.nd.array(rng.uniform(lo, hi, shape).astype(dtype))


# hand specs for ops whose input contracts the generic rules can't
# infer (shape coupling between inputs, packed encodings, special
# dtypes). Everything else is auto-generated.
_OP_OVERRIDES = {
    # layout NTC: pred (batch, seq, alphabet); label (batch, max_len)
    "CTCLoss": lambda: ([_mk("p", (4, 10, 8)),
                         _mk("l", (4, 2), "int32", 1, 7)], {}),
    "MultiBoxTarget": lambda: ([_mk("a", (1, 16, 4), lo=0.0, hi=1.0),
                                _mk("l", (2, 2, 5), lo=0.1, hi=0.5),
                                _mk("c", (2, 2, 16))], {}),
    # default scales x ratios = 12 anchors: cls 2*12 ch, bbox 4*12 ch
    "Proposal": lambda: ([_mk("c", (1, 24, 8, 8)),
                          _mk("b", (1, 48, 8, 8), lo=-0.1, hi=0.1),
                          _mk("i", (1, 3), lo=8, hi=9)], {}),
    "MultiProposal": lambda: ([_mk("c", (1, 24, 8, 8)),
                               _mk("b", (1, 48, 8, 8), lo=-0.1, hi=0.1),
                               _mk("i", (1, 3), lo=8, hi=9)], {}),
    "GridGenerator": lambda: ([_mk("d", (2, 6))],
                              {"transform_type": "affine",
                               "target_shape": (4, 4)}),
    "SpatialTransformer": lambda: ([_mk("d", (2, 4, 8, 8)),
                                    _mk("l", (2, 6))],
                                   {"transform_type": "affine",
                                    "target_shape": (4, 4)}),
    "DeformableConvolution": lambda: (
        [_mk("d", (2, 4, 8, 8)), _mk("o", (2, 18, 8, 8), lo=-1, hi=1),
         _mk("w", (8, 4, 3, 3))],
        {"kernel": (3, 3), "num_filter": 8, "pad": (1, 1),
         "no_bias": True}),
    "Deconvolution": lambda: ([_mk("d", (2, 4, 8, 8)),
                               _mk("w", (4, 8, 3, 3))],
                              {"kernel": (3, 3), "num_filter": 8,
                               "no_bias": True}),
    "Pad": lambda: ([_mk("d", (2, 4, 8, 8))],
                    {"mode": "constant",
                     "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)}),
    "Reshape": lambda: ([_mk("d", (_B, _D))], {"shape": (_D, _B)}),
    "broadcast_to": lambda: ([_mk("d", (1, _D))], {"shape": (_B, _D)}),
    "cast_storage": lambda: ([_mk("d", (_B, _D))], {"stype": "default"}),
    "RNN": lambda: ([_mk("d", (5, 2, 8)), _mk("p", (4096,), lo=-0.1,
                                              hi=0.1),
                     _mk("s", (1, 2, 8))],
                    {"state_size": 8, "num_layers": 1,
                     "mode": "rnn_tanh"}),
    "gather_nd": lambda: ([_mk("d", (_B, _D)),
                           _mk("i", (2, 4), "int32", 0, 7)], {}),
    "scatter_nd": lambda: ([_mk("d", (4,)),
                            _mk("i", (1, 4), "int32", 0, 7)],
                           {"shape": (_B,)}),
    "_scatter_set_nd": lambda: ([_mk("d", (_B,)), _mk("v", (4,)),
                                 _mk("i", (1, 4), "int32", 0, 7)],
                                {"shape": (_B,)}),
    "choose_element_0index": lambda: ([_mk("d", (_B, _D)),
                                       _mk("i", (_B,), "int32", 0,
                                           _D - 1)], {}),
    "fill_element_0index": lambda: ([_mk("d", (_B, _D)),
                                     _mk("v", (_B,)),
                                     _mk("i", (_B,), "int32", 0,
                                         _D - 1)], {}),
    "_unravel_index": lambda: ([_mk("i", (_B,), "int32", 0, 63)],
                               {"shape": (8, 8)}),
    "_linalg_maketrian": lambda: ([_mk("d", (2, 36))], {}),
    "_contrib_quantized_conv": lambda: (
        [_mk("d", (2, 4, 8, 8), "int8", -127, 127),
         _mk("w", (8, 4, 3, 3), "int8", -127, 127),
         _mk("bz", (8,), "int8", -127, 127),
         _mk("mn", (1,), lo=-1, hi=-0.9), _mk("mx", (1,), lo=0.9, hi=1),
         _mk("wmn", (1,), lo=-1, hi=-0.9),
         _mk("wmx", (1,), lo=0.9, hi=1),
         _mk("bmn", (1,), lo=-1, hi=-0.9),
         _mk("bmx", (1,), lo=0.9, hi=1)],
        {"kernel": (3, 3), "num_filter": 8, "no_bias": True}),
    "_contrib_quantized_concat": lambda: (
        [_mk("a", (_B, _D), "int8", -127, 127),
         _mk("b", (_B, _D), "int8", -127, 127),
         _mk("amn", (1,), lo=-1, hi=-0.9), _mk("amx", (1,), lo=0.9, hi=1),
         _mk("bmn", (1,), lo=-1, hi=-0.9),
         _mk("bmx", (1,), lo=0.9, hi=1)],
        {"num_args": 2, "dim": 1}),
    "_contrib_calibrate_entropy": lambda: (
        [_mk("h", (64,), lo=0, hi=100),
         _mk("e", (65,), lo=-1, hi=1)], {"num_quantized_bins": 16}),
    "bernoulli": lambda: ([_mk("p", (_B, _D), lo=0.1, hi=0.9)], {}),
    # internal CSR kernel seam (ndarray/sparse.py): CSR structure rides
    # as static kwargs, so synthesize a consistent 8x32 sparse matrix
    "_sparse_dot_csr_dense": lambda: (
        [_mk("v", (64,)), _mk("d", (_D, 16))],
        {"col_indices": np.tile(np.arange(8) * 4, 8).astype(np.int64),
         "indptr": (np.arange(9) * 8).astype(np.int64),
         "num_rows": 8}),
    "negative": lambda: ([_mk("x", (_B, _D))], {}),
    "_contrib_hawkesll": lambda: (
        [_mk("mu", (2, 3), lo=0.1, hi=0.5),
         _mk("al", (3,), lo=0.1, hi=0.4),
         _mk("be", (3,), lo=0.5, hi=1.0),
         _mk("st", (2, 3), lo=0.5, hi=1.0),
         _mk("lags", (2, 5), lo=0.01, hi=0.2),
         _mk("marks", (2, 5), "int32", 0, 2),
         _mk("vl", (2,), "int32", 4, 5),
         _mk("maxt", (2,), lo=2.0, hi=3.0)], {}),
}


def _upd(n_tensors, **hyper):
    """Fused-optimizer update-op spec: n same-shape tensors (weight +
    grad + states) plus runtime hyperparameters. lr etc. default to
    None in the registry fns but are REQUIRED by the generated nd
    wrappers (the reference marks them required attrs), so auto_spec's
    optional-param skip can't synthesize them."""
    def make():
        return ([_mk("t%d" % i, (_B, _D), seed=i)
                 for i in range(n_tensors)], dict(hyper))
    return make


def _multi_upd(n_per, groups=2, preloaded=False):
    """multi_* update ops: `groups` interleaved (weight, grad, states)
    tuples; preloaded variants carry the lr/wd vectors as the two
    trailing DATA tensors instead of attrs."""
    def make():
        args = [_mk("m%d" % i, (_B, _D), seed=i)
                for i in range(n_per * groups)]
        if preloaded:
            args += [_mk("lrs", (groups,), lo=0.01, hi=0.1),
                     _mk("wds", (groups,), lo=0.0, hi=0.01)]
            return args, {"num_weights": groups}
        return args, {"num_weights": groups,
                      "lrs": [0.05] * groups, "wds": [0.0] * groups}
    return make


_OP_OVERRIDES.update({
    "sgd_update": _upd(2, lr=0.05),
    "sgd_mom_update": _upd(3, lr=0.05),
    "mp_sgd_update": _upd(3, lr=0.05),
    "mp_sgd_mom_update": _upd(4, lr=0.05),
    "signsgd_update": _upd(2, lr=0.05),
    "signum_update": _upd(3, lr=0.05),
    "nag_mom_update": _upd(3, lr=0.05),
    "mp_nag_mom_update": _upd(4, lr=0.05),
    "adam_update": _upd(4, lr=0.05),
    "ftml_update": _upd(5, lr=0.05, t=1),
    "ftrl_update": _upd(4, lr=0.05),
    "rmsprop_update": _upd(3, lr=0.05),
    "rmspropalex_update": _upd(5, lr=0.05),
    "adamw_update": _upd(4, rescale_grad=1.0, lr=0.05, eta=1.0),
    "mp_adamw_update": _upd(5, rescale_grad=1.0, lr=0.05, eta=1.0),
    "lamb_update_phase1": _upd(4, lr=0.05),
    # phase2's r1/r2 are the per-tensor scalar norms, shape (1,) — a
    # full-tensor ratio would time a different computation
    "lamb_update_phase2": lambda: (
        [_mk("w", (_B, _D)), _mk("g", (_B, _D), seed=1),
         _mk("r1", (1,), lo=1.0, hi=2.0), _mk("r2", (1,), lo=1.0, hi=2.0)],
        {"lr": 0.05}),
    "group_adagrad_update": _upd(3, lr=0.05),
    "multi_sgd_update": _multi_upd(2),
    "multi_sgd_mom_update": _multi_upd(3),
    "multi_mp_sgd_update": _multi_upd(3),
    "multi_mp_sgd_mom_update": _multi_upd(4),
    "preloaded_multi_sgd_update": _multi_upd(2, preloaded=True),
    "preloaded_multi_sgd_mom_update": _multi_upd(3, preloaded=True),
    "preloaded_multi_mp_sgd_update": _multi_upd(3, preloaded=True),
    "preloaded_multi_mp_sgd_mom_update": _multi_upd(4, preloaded=True),
    # creation ops whose nd wrapper exposes required positionals
    # (val / stop) under different names than the registry fn
    "full": lambda: ([(_B, _D), 2.0], {}),
    "arange": lambda: ([0.0, float(_B * _D)], {}),
})

# values for REQUIRED static params, by name (optional params keep their
# defaults)
_STATIC_DEFAULTS = {
    "kernel": (3, 3), "num_filter": 8, "num_hidden": _D,
    "shape": (_B * _D,), "axis": 0, "axes": None, "dim": 0,
    "depth": 16, "reps": (2, 2), "size": 2, "k": 1, "begin": 0, "end": 4,
    "scalar": 2.0, "p": 0.5, "num_outputs": 2, "num_args": 2,
    "pooled_size": 2, "output_dim": 4, "spatial_scale": 1.0,
    "group_size": 2, "rhs_begin": 0, "rhs_end": 1, "lhs_begin": 0,
    "lhs_end": 1, "num_group": 1, "eps": 1e-5, "dtype": "float32",
    "src_dtype": "float32", "target_dtype": "float32",
    "sample_ratio": 1, "state_size": _D, "num_layers": 1, "mode": "rnn_tanh",
    "act_type": "relu", "transform_type": "affine", "target_shape": (4, 4),
    "min_calib_range": -1.0, "max_calib_range": 1.0, "nms_threshold": 0.5,
    "overlap_threshold": 0.5, "n": 2, "num_sampled": 4, "range_max": 16,
    "slice_mode": "center",
}


def _make_tensor(name, seed, profile):
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    shape = profile.get(name) or _SHARED_SHAPES.get(name) or (_B, _D)
    if name in _INT_TENSORS:
        return mx.nd.array(rng.randint(0, 4, shape).astype("int32"))
    return mx.nd.array(rng.uniform(0.5, 1.5, shape).astype("float32"))


def auto_spec(opdef, profile):
    """Synthesize (args, kwargs) for an op from its fn signature using
    one shape profile, or raise ValueError naming what could not be
    synthesized. Rule: every leading required parameter that is not a
    known static is a tensor input (the registry convention the symbol
    wrappers also rely on)."""
    import inspect
    sig = inspect.signature(opdef.fn)
    args = []
    kwargs = {}
    in_input_prefix = True
    seed = 0
    for p in sig.parameters.values():
        if p.kind == inspect.Parameter.VAR_POSITIONAL:
            # variadic ops get two tensors
            args.extend([_make_tensor("data", 0, profile),
                         _make_tensor("data", 1, profile)])
            in_input_prefix = False
            continue
        if p.kind == inspect.Parameter.VAR_KEYWORD:
            continue
        if p.name in ("key", "_training", "out", "name"):
            continue
        required = p.default is inspect.Parameter.empty
        if in_input_prefix and required and \
                p.name not in _STATIC_DEFAULTS:
            args.append(_make_tensor(p.name, seed, profile))
            seed += 1
            continue
        in_input_prefix = False
        if not required:
            continue  # optional static: keep the default
        if p.name in _STATIC_DEFAULTS:
            v = _STATIC_DEFAULTS[p.name]
            if v is not None:
                kwargs[p.name] = v
            continue
        raise ValueError("no synthesis rule for required param %r"
                         % p.name)
    if not args and "shape" not in kwargs:
        # creation ops (zeros/arange/samplers) run tensor-free if they
        # accept a shape
        if "shape" in sig.parameters:
            kwargs["shape"] = (_B, _D)
        else:
            raise ValueError("op takes no tensor inputs")
    return args, kwargs


def _bench_callable(fn, runs, warmup):
    """Per-call synchronous timing: every iteration blocks until ready,
    so no async pipelining can hide (or fabricate) dispatch cost. This
    is a HOST-side microbench harness: per-call sync is part of what
    it times."""
    import jax

    def _ready(out):
        leaves = out if isinstance(out, (tuple, list)) else [out]
        jax.block_until_ready([getattr(o, "_data", o) for o in leaves
                               if o is not None])

    for _ in range(max(warmup, 1)):
        _ready(fn())
    t0 = time.perf_counter()
    for _ in range(runs):
        _ready(fn())
    return (time.perf_counter() - t0) / runs * 1e3


def bench_registry_op(name, opdef, runs=5, warmup=1):
    """Benchmark one registry op with auto inputs: the mx.nd dispatch
    path AND the jnp-native baseline (calling the registered pure fn on
    raw jax arrays — the lower bound the dispatch layer adds overhead
    to). Input shapes come from the first profile the op accepts."""
    import inspect
    import jax
    import mxnet_tpu as mx

    fn = getattr(mx.nd, name, None)
    if fn is None:
        # ops registered after namespace population (internal seams
        # like _sparse_dot_csr_dense) still dispatch via the registry;
        # bind the opdef once so the timed loop pays the same dispatch
        # cost as mx.nd-exposed ops (no per-call name lookup)
        from mxnet_tpu.ndarray.register import invoke as _invoke
        fn = lambda *a, **kw: _invoke(opdef, a, kw)  # noqa: E731
    args = kwargs = None
    last_err = None
    if name in _OP_OVERRIDES:
        args, kwargs = _OP_OVERRIDES[name]()
    else:
        for profile in _PROFILES:
            try:
                cand_args, cand_kwargs = auto_spec(opdef, profile)
                fn(*cand_args, **cand_kwargs)  # dry run, this profile
                args, kwargs = cand_args, cand_kwargs
                break
            except Exception as e:  # noqa: BLE001 — next rank profile
                last_err = e
        if args is None:
            # creation ops whose params all default (arange/eye/window
            # fns/samplers): run argument-free
            try:
                fn()
                args, kwargs = [], {}
            except Exception:  # noqa: BLE001
                raise last_err
    nd_ms = _bench_callable(lambda: fn(*args, **kwargs), runs, warmup)

    # jnp-native baseline: the raw registered function
    raw = [getattr(a, "_data", a) for a in args]
    sig = inspect.signature(opdef.fn)
    extra = {}
    if "key" in sig.parameters:
        extra["key"] = jax.random.PRNGKey(0)
    if "_training" in sig.parameters:
        extra["_training"] = False
    base_ms = _bench_callable(
        lambda: opdef.fn(*raw, **kwargs, **extra), runs, warmup)
    return {"op": name, "fwd_ms": round(nd_ms, 4),
            "jnp_native_ms": round(base_ms, 4),
            "dispatch_overhead_ms": round(nd_ms - base_ms, 4)}


# pseudo-ops that are not benchmarkable operators: fused subgraph
# regions are graph-local artifacts (symbol/subgraph.py registers one
# per partition call), and Custom is the Python-callback bridge whose
# inputs are defined by the user callback, not a signature
_SKIP_PREFIXES = ("_subgraph_",)
_SKIP_OPS = {"Custom"}


def run_full_registry(runs=5, warmup=1, verbose=False, ops=None):
    """One command over EVERY registered op name (aliases share their
    canonical OpDef's measurement; `ops` filters to a subset by any
    registered name). Forward-path timing only. Returns the summary
    dict that --full emits as JSON."""
    from mxnet_tpu.ops import registry as _registry

    names = [n for n in _registry.list_ops()
             if n not in _SKIP_OPS
             and not n.startswith(_SKIP_PREFIXES)]
    skipped = len(_registry.list_ops()) - len(names)
    canonical = {}
    for n in names:
        opdef = _registry.get_op(n)
        # canonical = any registered name with a hand spec, else the
        # first seen — so _OP_OVERRIDES keys match regardless of how
        # alias names sort
        if n in _OP_OVERRIDES or id(opdef) not in canonical:
            canonical[id(opdef)] = n

    if ops:
        filtered = [n for n in ops
                    if n in _SKIP_OPS or n.startswith(_SKIP_PREFIXES)]
        if filtered:
            raise ValueError(
                "requested pseudo-ops are not benchmarkable: %s"
                % filtered)
        wanted = {id(_registry.get_op(n)) for n in ops}
        canonical = {k: v for k, v in canonical.items() if k in wanted}

    results, errors = {}, {}
    for _oid, cname in sorted(canonical.items(), key=lambda kv: kv[1]):
        opdef = _registry.get_op(cname)
        try:
            results[cname] = bench_registry_op(cname, opdef, runs, warmup)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            errors[cname] = "%s: %s" % (type(e).__name__, str(e)[:100])
        if verbose:
            status = "ok" if cname in results else "ERR"
            print("%-40s %s" % (cname, status), file=sys.stderr)

    ok = sorted(results.values(), key=lambda r: -r["fwd_ms"])
    return {
        "registry_names": len(names),
        "skipped_pseudo_ops": skipped,
        "unique_ops": len(canonical),
        "measured": len(results),
        "errors": len(errors),
        "coverage_pct": round(100.0 * len(results)
                              / max(len(canonical), 1), 1),
        "top10_slowest": ok[:10],
        "results": results,
        "error_detail": errors,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="op micro-benchmarks (ref: benchmark/opperf)")
    parser.add_argument("--ops", default=None,
                        help="comma-separated op subset")
    parser.add_argument("--runs", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--no-backward", action="store_true")
    parser.add_argument("--full", action="store_true",
                        help="sweep EVERY registered op with "
                             "auto-generated inputs (small shapes)")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--json", default=None, help="write results here")
    args = parser.parse_args(argv)
    if args.full:
        ops = args.ops.split(",") if args.ops else None
        summary = run_full_registry(runs=max(1, args.runs // 4),
                                    warmup=args.warmup,
                                    verbose=args.verbose, ops=ops)
        print("registry names: %d (unique ops %d), measured %d, "
              "errors %d -> %.1f%% coverage (forward-path timing)"
              % (summary["registry_names"], summary["unique_ops"],
                 summary["measured"], summary["errors"],
                 summary["coverage_pct"]))
        print("%-36s %10s %14s" % ("10 slowest", "fwd (ms)",
                                   "jnp-native (ms)"))
        for r in summary["top10_slowest"]:
            print("%-36s %10.4f %14.4f" % (r["op"], r["fwd_ms"],
                                           r["jnp_native_ms"]))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2)
        return 0
    ops = args.ops.split(",") if args.ops else None
    results = run_performance_test(ops, args.warmup, args.runs,
                                   not args.no_backward)
    print("%-18s %12s %12s" % ("op", "fwd (ms)", "fwd+bwd (ms)"))
    for r in results:
        if "error" in r:
            print("%-18s ERROR: %s" % (r["op"], r["error"]))
        else:
            print("%-18s %12.4f %12s" % (
                r["op"], r["fwd_ms"],
                "%.4f" % r["fwd_bwd_ms"] if r["fwd_bwd_ms"] else "-"))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())

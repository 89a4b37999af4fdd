"""Fused train step: loss-forward + backward + optimizer update as ONE
donated jitted program.

The reference's biggest training-throughput lever is CachedOp with
``static_alloc``/``static_shape`` (ref: src/imperative/cached_op.cc —
plan memory once, reuse buffers, run the whole graph as one segment).
Our hybridize analog only jits the *forward*: backward replays the tape
as a separate vjp program and ``Trainer._update`` dispatches one
optimizer call per parameter per step, double-buffering weights and
optimizer state. For a ResNet/transformer step that host-side loop is
the dominant overhead — it spans autograd and the optimizer, so neither
the PR 1 eager fast path nor the HybridBlock cache can reach it.

``FusedTrainStep`` closes the loop: one ``jax.jit`` program traces

    loss = loss_fn(...)                  # forward
    grads = d loss / d params            # whole-graph backward (jax.vjp)
    w', s' = step_fn(w, g, s, lr, wd, r) # optimizer, all params at once

with parameter and optimizer-state buffers DONATED to XLA (off-CPU), so
weights update in place instead of being double-buffered — the
``static_alloc`` analog for the whole step. Per-step hyperparameters
(lr, wd, rescale_grad) enter as TRACED OPERANDS, never baked constants:
an lr schedule tick or a new ``batch_size`` divisor replays the same
executable (``fused_step.retraces == 0``). Programs are cached with the
same signature-keyed compile-on-repeat pattern as the imperative
dispatch cache (ndarray/register.py): a signature runs the genuine
eager path until it repeats, so one-shot shapes never pay a trace.

Anything the trace can't honor falls back to the eager
record/backward/``Trainer.step`` path for THAT step — never a crash —
and is tallied in ``fused_step.fallbacks``: the env kill switch
(``MXNET_GLUON_FUSED_STEP=0``), an active ``autograd.record`` scope, an
attached kvstore (multi-host reduce happens outside the program),
sparse grads, ``grad_req='add'``, a non-hybridized block handed to
``train_step``, optimizers without the pure ``step_fn`` form, and
deferred-init parameters (the eager step initializes them; later steps
fuse). Counters surface as ``profiler.metrics()['fused_step']`` and
each call is a ``gluon.train_step`` span in the profiler's ``gluon``
lane.

API::

    step = trainer.fuse_step(lambda x, y: loss(net(x), y))
    step = mxnet_tpu.gluon.train_step(net, loss, trainer)   # block form
    for x, y in batches:
        l = step(x, y, batch_size=x.shape[0])
"""
from __future__ import annotations

import functools
import inspect
import os
import time as _time
import warnings
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from .. import profiler as _profiler
from ..base import getenv as _getenv
from .. import random as _random
from ..ndarray import NDArray
from ..ndarray import register as _register
from .._debug import faultpoint as _faultpoint
from .._debug import flightrec as _flightrec
from .._debug import healthmon as _healthmon
from .._debug import watchdog as _watchdog
from .. import storage as _storage
from ..optimizer.optimizer import _is_low_precision
from . import compile_cache as _compile_cache
from .block import make_pure_forward

__all__ = ["FusedTrainStep", "train_step", "fused_step_enabled",
           "set_fused_step", "stats", "reset_stats"]

_ENABLED = _getenv("MXNET_GLUON_FUSED_STEP", "1") \
    not in ("0", "false", "off")
# compile a signature only once it repeats (one-shot shapes stay on the
# genuine eager path) — same contract as register._JIT_THRESHOLD
_COMPILE_THRESHOLD = 2
_CACHE_CAP = 64  # per-step-object; shape churn clears rather than grows

# mxlint: disable=MX003 (GIL-atomic best-effort counters, same contract as ndarray/register._STATS)
_STATS = {
    "hits": 0,       # step served by a cached compiled program
    "misses": 0,     # signature not yet compiled (eager warming, or
                     # compiled this call)
    "retraces": 0,   # compile for a config seen before with different
                     # input/param avals — shape churn indicator
    "fallbacks": 0,  # step took the eager path for an eligibility or
                     # trace-failure reason (see the span's mode arg)
    "attr_errors": 0,  # compile-attribution bookkeeping failed after a
                       # committed compile step (telemetry lost, step kept)
    "health_errors": 0,  # healthmon.note_step raised after a committed
                         # program (sentinel verdict lost, step kept —
                         # a telemetry failure must not skip adoption)
    "mesh_fallbacks": 0,  # mesh-mode steps demoted to eager because the
                          # batch dim does not divide the 'dp' axis —
                          # every such step pays the single-device eager
                          # cost (the warn-once + flightrec marker make
                          # a 10x slowdown name itself)
}


def fused_step_enabled():
    return _ENABLED


def set_fused_step(enabled):
    """Toggle the fused train step at runtime (the env var
    ``MXNET_GLUON_FUSED_STEP`` sets the process default). Returns the
    previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(enabled)
    return prev


def stats():
    """Snapshot of the fused-step counters
    (hits/misses/retraces/fallbacks)."""
    return dict(_STATS)


def reset_stats():
    for k in _STATS:
        _STATS[k] = 0


# surfaces as metrics()['fused_step'] and a dumps() line
_profiler.register_stats_provider("fused_step", stats, reset_stats)


# benchmark/comm_model.py is the ONE home of the wire-time formula and
# the v5e model assumptions (deduped there by the PR 7 review); it
# lives beside the package, not inside it, so load it by path. Only a
# tree without the file (an installed wheel without the benchmark/ dir)
# runs attribution-less; a file that is there and fails to load raises.
_COMM_MODEL_UNSET = object()
_COMM_MODEL = _COMM_MODEL_UNSET


def _load_comm_model():
    global _COMM_MODEL
    if _COMM_MODEL is _COMM_MODEL_UNSET:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "benchmark", "comm_model.py")
        if not os.path.isfile(path):
            _COMM_MODEL = None
            return None
        spec = importlib.util.spec_from_file_location(
            "_mxtpu_comm_model", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _COMM_MODEL = mod
    return _COMM_MODEL


def _state_to_data(state):
    """NDArray state tree -> jax-array pytree (None passes through)."""
    if state is None:
        return None
    if isinstance(state, NDArray):
        return state._data
    if isinstance(state, (tuple, list)):
        return tuple(_state_to_data(s) for s in state)
    return state


def _adopt_state(state, new):
    """Write a returned jax-array pytree back into the NDArray state
    tree in place (the pending-result adoption of optimizer state).
    Fresh buffers re-register in the allocation ledger; the replaced
    ones retire via weakref death / donation ``is_deleted()``."""
    if state is None:
        return
    if isinstance(state, NDArray):
        state._data = new
        _storage.ledger_register(new, "opt_state", site="fused_step")
        return
    for s, n in zip(state, new):
        _adopt_state(s, n)


def train_step(block, loss_fn, trainer, mesh=None, bucket_bytes=None,
               rules=None):
    """Fused training step for a (block, loss, trainer) triple:
    ``step(data, label, batch_size=...)`` computes
    ``loss_fn(block(data), label)``, backpropagates, and applies the
    trainer's optimizer — all inside one donated jitted program when the
    block is hybridized (eager fallback otherwise, tallied, never a
    crash). With more than two positional args, all but the last feed
    the block and the last is the label. Returns the loss NDArray, like
    the eager ``loss_fn`` call would.

    With ``mesh`` (a ``parallel.create_mesh`` DeviceMesh), the program
    runs data-parallel over the mesh's 'dp' axis inside ``shard_map``:
    the batch is sharded, parameters stay replicated, and the gradient
    all-reduce is issued as size-capped buckets placed MID-BACKWARD
    (``parallel/overlap.py``) so the reduction hides under the backward
    instead of serializing after it — the SCALING_r05 overlap story,
    folded into the fused step.

    With a 3D dp×tp×sp mesh (any model axis >1) or explicit ``rules``
    (regex partition rules over the param tree —
    ``parallel/sharding.PartitionRules``, a ``ShardingStrategy``, or a
    raw ``[(regex, spec)]`` list), the program runs in GSPMD mode
    instead: params carry NamedShardings from the rules, the batch is
    sharded over dp (and sp when it divides), the SPMD partitioner
    inserts the collectives, and the step's ``out_shardings`` are
    matched to its ``in_shardings`` so donated weights/optimizer state
    never reshard between steps (see docs/PARALLEL.md)."""
    return FusedTrainStep(trainer, loss_fn, block=block, mesh=mesh,
                          bucket_bytes=bucket_bytes, rules=rules)


class FusedTrainStep:
    """One training step as one XLA program (see the module docstring).

    Built via ``Trainer.fuse_step(loss_fn)`` (``loss_fn(*batch)`` is any
    callable over NDArrays returning the per-sample loss, usually a
    closure over the net) or ``gluon.train_step(block, loss_fn,
    trainer)``. In the closure form, parameters NOT owned by the trainer
    are baked into the program as constants — keep everything the loss
    reads inside the trainer (or use the block form, which threads every
    block parameter through the trace)."""

    def __init__(self, trainer, loss_fn, block=None, mesh=None,
                 bucket_bytes=None, rules=None):
        if not callable(loss_fn):
            raise TypeError("loss_fn must be callable, got %r"
                            % type(loss_fn))
        self._trainer = trainer
        self._block = block
        self._mesh = mesh
        self._bucket_bytes = bucket_bytes
        self._rules_arg = rules
        self._rules = None       # resolved PartitionRules (GSPMD mode)
        self._dp = 1
        self._sizes = {}
        self._mesh_n = 1
        self._warned_mesh_indivisible = False
        self._last_compiled = None  # most recent AOT executable (mesh)
        self._last_hlo = None       # ... and its optimized HLO text
        self._build_info = None     # contract facts of the last _build
        if mesh is not None:
            raw = getattr(mesh, "mesh", mesh)
            self._sizes = {a: int(s) for a, s in dict(raw.shape).items()}
            self._dp = int(self._sizes.get("dp", 1))
            self._mesh_n = 1
            for s in self._sizes.values():
                self._mesh_n *= int(s)
            # the Trainer/loss ce_local_accum weld: a mesh-aware loss
            # (e.g. a closure over parallel/transformer.loss_fn, which
            # auto-selects the single-reduction chunked CE) declares a
            # ``mesh`` kwarg and receives THIS step's mesh — no side
            # channel, the one mesh drives data, params and the loss
            try:
                if "mesh" in inspect.signature(loss_fn).parameters:
                    loss_fn = functools.partial(loss_fn, mesh=mesh)
            except (TypeError, ValueError):
                pass
        self._loss_fn = loss_fn
        self._cache = {}  # full signature ->
        #   (jfn, aux_params, fixed_pos, hmeta, in_shardings)
        self._key_counts = {}   # signature -> times seen (warming)
        self._partial_keys = set()  # configs compiled (retrace detection)
        self._failed_keys = set()   # signatures that failed to trace
        self.last_trace_error = None  # the exception behind the most
        #                               recent fallback:trace-failed
        self.last_mode = None   # how the previous call executed
        self._aot = None        # (compiled, cost, hlo) from the last AOT
        self._ckey = None       # full signature key of the in-flight
        #                         compile; _run's AOT branch keys the
        #                         persistent compile cache by it
        self._aot_from_cache = False  # last AOT came off disk, so
        #                               _record_compile must not
        #                               re-serialize it back
        # signature -> modeled compute/comm split (ISSUE 8c): keyed like
        # _cache so a run alternating compiled signatures (main batch +
        # remainder shape) never subtracts the OTHER program's modeled
        # device time from this step's wall time
        self._attr_models = {}
        self._step_attr = None  # the executing step's model (set by hits)

    # -- mesh-mode selection -----------------------------------------------
    def _gspmd_mode(self):
        """True when this step compiles as one GSPMD program (jit with
        explicit in/out shardings) instead of the dp-only shard_map:
        any model axis of the mesh >1, or explicit partition rules.
        ``MXTPU_GSPMD_STEP=0`` (a compile-signature token) forces the
        legacy treatment — params replicated, batch dp-sharded — as the
        escape hatch for partitioner bugs; the token makes the flip
        land on a fresh cache key."""
        if self._mesh is None:
            return False
        model_axes = any(int(self._sizes.get(a, 1)) > 1
                         for a in ("tp", "sp", "fsdp", "ep", "pp"))
        if not (model_axes or self._rules_arg is not None):
            return False
        return _getenv("MXTPU_GSPMD_STEP", "1") not in ("0", "false",
                                                        "off")

    def _resolve_rules(self):
        """The partition rules the GSPMD mode shards params by: the
        constructor's ``rules`` (PartitionRules / ShardingStrategy /
        raw list), else inferred from the block's param paths
        (``sharding.infer_rules_for_block(..., 'auto')`` — Megatron TP
        rules when they match, replicated otherwise)."""
        if self._rules is not None:
            return self._rules
        from ..parallel import sharding as _sharding
        rules = self._rules_arg
        if rules is None:
            rules = _sharding.infer_rules_for_block(
                self._block, self._mesh, "auto")
        if isinstance(rules, _sharding.ShardingStrategy):
            rules = rules.param_rules
        elif not isinstance(rules, _sharding.PartitionRules):
            rules = _sharding.PartitionRules(rules)
        self._rules = rules
        return rules

    def last_program(self):
        """(compiled_executable, optimized_hlo_text) of the most recent
        AOT-compiled signature, or (None, None). The bench gspmd_step
        gate and the comm tests measure collective payloads from the
        HLO and check the matched-shardings contract on the
        executable."""
        return self._last_compiled, self._last_hlo

    def matched_step_shardings(self):
        """The SNIPPETS [1] zero-resharding contract, checked on the
        compiled program: the weight/optimizer-state OUTPUT shardings
        equal the corresponding INPUT shardings, so step N's donated
        outputs feed step N+1 without a single resharding transfer.
        Returns True/False, or None when no AOT program is held."""
        compiled = self._last_compiled
        if compiled is None:
            return None
        try:
            in_shs = compiled.input_shardings[0]
            out_shs = compiled.output_shardings
        except Exception:
            return None

        def _specs(tree):
            return [getattr(s, "spec", s) for s in
                    jax.tree_util.tree_leaves(tree)]

        n_train = len(_specs(in_shs[0]))
        n_state = len(_specs(in_shs[1]))
        # outputs: (loss, new_ws, new_sts, grads, aux[, health])
        return (_specs(out_shs[1]) == _specs(in_shs[0])
                and _specs(out_shs[2]) == _specs(in_shs[1])
                and n_train > 0 and n_state >= 0)

    # -- public ------------------------------------------------------------
    def __call__(self, *args, batch_size=None, ignore_stale_grad=False):
        from ..ndarray import array as _nd_array
        nd_args = [a if isinstance(a, NDArray) else _nd_array(a)
                   for a in args]
        if batch_size is None:
            batch_size = int(nd_args[0].shape[0]) \
                if nd_args and nd_args[0].shape else 1
        # watchdog beacon: the outermost in-flight step the stall
        # detector watches; non-"fused" completions are warm-up/compile/
        # fallback shapes and stay out of the rolling median
        _watchdog.step_begin()
        # the program's step span: on the device trace's host plane
        # when xprof runs, and through record_op under _LIVE on exit
        sp = _profiler.step_span("gluon.train_step", lane="gluon",
                                 category="gluon")
        sp.__enter__()
        mode = "error"
        try:
            loss, mode = self._dispatch(nd_args, batch_size,
                                        ignore_stale_grad)
        finally:
            self.last_mode = mode
            # mode rides the beacon so the goodput run ledger can split
            # step wall time into compute ('fused') vs compile
            # ('compile'/'eager-warming') vs host-bound fallbacks; the
            # executing program's signature tag rides along (one tuple
            # field) keying the watchdog window + the roofline join
            attr = self._step_attr if mode == "fused" else None
            _watchdog.step_end(warmup=mode != "fused", mode=mode,
                               sig=attr.get("sig") if attr else None)
            if _profiler._LIVE:
                sp.args = {"mode": mode, "batch_size": batch_size,
                           "params": len(self._trainer._params)}
            sp.__exit__(None, None, None)
            if _profiler._LIVE:
                dur_us = sp.dur_us
                # the latency histogram ROADMAP item 1's serve gate
                # reports p50/p99 from (metrics()['latency'])
                _profiler.record_latency("fused_step.step", dur_us)
                if mode == "fused" and self._step_attr is not None:
                    # host share of THIS step = measured wall minus the
                    # modeled device time of the program that EXECUTED
                    # it — the latency series behind the dumps()
                    # attribution row
                    if self._step_attr["device_us"] > 0:
                        host = dur_us - self._step_attr["device_us"]
                        if host > 0:
                            _profiler.record_latency(
                                "fused_step.host_us", host)
                    # per-step memory.headroom gauge (ISSUE 13b): the
                    # EXECUTING signature's modeled peak vs the
                    # framework-side measured peak vs the device limit
                    # (cached snapshot — no backend walk per step)
                    if _profiler._ACTIVE and \
                            self._step_attr.get("peak_bytes"):
                        hr = _storage.headroom(
                            self._step_attr["peak_bytes"])
                        if hr:
                            _profiler.record_counter(
                                "memory.headroom", 0, lane="memory",
                                series=hr)
        return loss

    # -- dispatch ----------------------------------------------------------
    def _dispatch(self, nd_args, batch_size, ignore_stale_grad):
        reason = self._fallback_reason()
        if reason is None and self._mesh is not None and nd_args \
                and nd_args[0].shape \
                and nd_args[0].shape[0] % max(self._dp, 1) != 0:
            # the mesh step shards dim 0 over 'dp'; an indivisible batch
            # runs this step eagerly instead of crashing the trace.
            # Eager means SINGLE-DEVICE: a run whose loader emits such
            # batches silently pays ~mesh-size x per step, so the
            # demotion is never silent — a warn-once, a dedicated
            # counter, and a flight-recorder marker per occurrence
            reason = "mesh-batch-indivisible"
            _STATS["mesh_fallbacks"] += 1
            batch = int(nd_args[0].shape[0])
            if not self._warned_mesh_indivisible:
                self._warned_mesh_indivisible = True
                warnings.warn(
                    "fused step: batch dim %d does not divide mesh axis "
                    "dp=%d; this step (and every step with such a batch)"
                    " runs EAGERLY on one device. Pad or drop the "
                    "remainder batch, or size the loader batch to a "
                    "multiple of dp. (warn-once; see "
                    "fused_step.mesh_fallbacks in profiler.metrics())"
                    % (batch, self._dp), stacklevel=3)
            # mxlint: disable=MX011 (demotion path, not steady-state dispatch; the black box must see it with the profiler off)
            _flightrec.record_marker(
                "fused_step.mesh_fallback",
                args={"batch": batch, "dp": self._dp})
        if reason is None:
            all_params, train_pos, indices = self._param_split()
            if not train_pos:
                reason = "no-trainable-params"
            elif any(p._data is None for p in all_params):
                # covers block params the trainer does NOT own (frozen
                # layers): the eager step's forward finishes their
                # deferred init, later steps fuse
                reason = "deferred-init"
        if reason is not None:
            _STATS["fallbacks"] += 1
            return self._eager_step(nd_args, batch_size,
                                    ignore_stale_grad), \
                "fallback:" + reason

        # optimizer states are created HERE (not at update time) through
        # the trainer's own updater, so save_states/load_states round-trip
        # across eager and fused steps against one shared store
        updater = self._trainer._updater
        states = [updater.ensure_state(i, self._trainer._params[i].data())
                  for i in indices]
        key, partial = self._signature(nd_args, all_params, train_pos,
                                       states)
        if key in self._failed_keys:
            _STATS["fallbacks"] += 1
            return self._eager_step(nd_args, batch_size,
                                    ignore_stale_grad), \
                "fallback:trace-failed"

        entry = self._cache.get(key)
        if entry is not None:
            _STATS["hits"] += 1
            self._step_attr = self._attr_models.get(key)
            return self._run(entry, all_params, train_pos, indices, states,
                            nd_args, batch_size), "fused"

        _STATS["misses"] += 1
        if len(self._key_counts) >= 4 * _CACHE_CAP:
            self._key_counts.clear()  # one-shot signatures must not leak
        seen = self._key_counts.get(key, 0) + 1
        self._key_counts[key] = seen
        if seen < _COMPILE_THRESHOLD:
            return self._eager_step(nd_args, batch_size,
                                    ignore_stale_grad), "eager-warming"
        if len(self._cache) >= _CACHE_CAP:
            self._cache.clear()
            self._partial_keys.clear()
            self._attr_models.clear()
        if partial in self._partial_keys:
            _STATS["retraces"] += 1
        self._partial_keys.add(partial)
        try:
            c0 = _time.perf_counter()
            self._aot = None
            self._ckey = key
            entry = self._build(all_params, train_pos, nd_args, states)
            loss = self._run(entry, all_params, train_pos, indices, states,
                             nd_args, batch_size, aot=True)
            if self._aot is not None:
                # keep the AOT-compiled executable: jit's internal cache
                # does not share the AOT compilation, so calling the
                # plain jitted fn next step would compile a second time
                compiled, cost, hlo, mem = self._aot
                entry = (compiled,) + tuple(entry[1:])
                self._aot = None
            else:
                compiled = cost = hlo = mem = None
            compile_us = (_time.perf_counter() - c0) * 1e6
        except _healthmon.HealthHaltError:
            # a poisoned compile step under MXTPU_HEALTH_ACTION=halt is
            # a detected anomaly, not a trace failure: the batch must
            # NOT silently re-run on the eager path
            raise
        except Exception as e:
            # trace-incompatible step (data-dependent control flow, host
            # callback, ...): remember the signature and run the genuine
            # eager path — never a crash, and never a silent one: the
            # exception stays on the step, is warned once and lands in
            # the flight record, because a compiler refusal on the chip
            # looks exactly like this
            if len(self._failed_keys) >= 4 * _CACHE_CAP:
                self._failed_keys.clear()  # shape churn must not leak keys
            self._failed_keys.add(key)
            _STATS["fallbacks"] += 1
            self._note_trace_failure(e)
            return self._eager_step(nd_args, batch_size,
                                    ignore_stale_grad), \
                "fallback:trace-failed"
        self._cache[key] = entry
        # attribution AFTER the step committed, outside the trace-failure
        # try: the step above already mutated params/optimizer state, so a
        # cost-model or JAX-API error here must neither re-run the batch
        # eagerly (double update) nor blacklist a signature that compiled
        try:
            self._record_compile(key, compile_us, cost, hlo, mem,
                                 all_params, train_pos, states=states,
                                 compiled=compiled)
        except Exception:
            self._attr_models.pop(key, None)
            _STATS["attr_errors"] += 1
        return loss, "compile"

    def _note_trace_failure(self, exc):
        """Keep the evidence of a trace/compile/first-run failure that
        demoted this signature to the eager path: the exception on the
        step object, one warning per step object, and a flight-recorder
        marker per failed signature."""
        first = self.last_trace_error is None
        self.last_trace_error = exc
        msg = "%s: %s" % (type(exc).__name__, str(exc)[:2000])
        if first:
            warnings.warn(
                "fused step: trace/compile failed, this signature runs "
                "EAGERLY from now on (fallback:trace-failed) — %s "
                "(warn-once; the exception is step.last_trace_error)"
                % msg, RuntimeWarning, stacklevel=4)
        # mxlint: disable=MX011 (demotion path, not steady-state dispatch; the black box must see it with the profiler off)
        _flightrec.record_marker("fused_step.trace_failed",
                                 args={"error": msg[:500]})

    def _fallback_reason(self):
        if not _ENABLED:
            return "disabled"
        if autograd.is_recording():
            return "recording-scope"
        tr = self._trainer
        # mirror the eager step() prologue so eligibility sees the real
        # kvstore/params state (both calls are idempotent)
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        if tr._kvstore is not None:
            return "kvstore"
        if not tr._optimizer.fused_step_supported():
            return "optimizer:" + type(tr._optimizer).__name__
        if hasattr(tr, "_amp_loss_scaler"):
            # amp.init_trainer wraps Trainer._update with the dynamic
            # loss-scaler overflow skip — logic the fused program would
            # silently bypass
            return "amp-loss-scaler"
        if self._block is not None and \
                not getattr(self._block, "_active", False):
            return "non-hybridized"
        for p in tr._params:
            if p.grad_req == "add":
                return "grad-req-add"
            if getattr(p, "_grad_stype", "default") != "default" or \
                    getattr(p, "_stype", "default") != "default":
                return "sparse-grad"
        return None

    def _param_split(self):
        """(all_params, trainable positions, trainer indices). The block
        form threads EVERY block parameter through the trace (frozen ones
        as runtime inputs, not baked constants); the closure form can only
        see the trainer's."""
        tr = self._trainer
        if self._block is not None:
            all_params = self._block._all_params_list()
            known = {id(p) for p in all_params}
            all_params = all_params + [p for p in tr._params
                                       if id(p) not in known]
        else:
            all_params = list(tr._params)
        train_pos, indices = [], []
        for pos, p in enumerate(all_params):
            idx = tr._param2idx.get(p.name)
            if idx is not None and tr._params[idx] is p \
                    and p.grad_req != "null":
                train_pos.append(pos)
                indices.append(idx)
        return all_params, train_pos, indices

    def _signature(self, nd_args, all_params, train_pos, states):
        """(full cache key, partial key). lr/wd/rescale are operands and
        deliberately absent; the partial key (config without avals) is the
        retrace detector, same contract as register._dispatch_key."""
        state_datas = [_state_to_data(s) for s in states]
        mesh_fp = None
        if self._mesh is not None:
            # mode fingerprint: GSPMD vs dp-shard_map, the mesh axis
            # sizes, and (GSPMD) the partition-rule table — editing a
            # rule or resizing an axis must land on a fresh program,
            # never replay one compiled for another layout
            gspmd = self._gspmd_mode()
            mesh_fp = (gspmd, tuple(sorted(self._sizes.items())),
                       self._resolve_rules().describe() if gspmd
                       else None)
        partial = (self._trainer._optimizer._fused_static_key(),
                   len(all_params), tuple(train_pos),
                   mesh_fp,
                   _register._amp_version,
                   # the signature-token registry: every env var that
                   # changes a traced graph (the packed-apply toggle for
                   # the update phase, the kernel-routing envs for the
                   # forward) — flipping any of them mid-run must
                   # recompile, not silently replay the other form
                   _register.signature_tokens(),
                   jax.tree_util.tree_structure(state_datas))
        full = partial + (
            tuple(_register.aval(a._data) for a in nd_args),
            tuple(_register.aval(p.data()._data) for p in all_params),
            tuple(_register.aval(l)
                  for l in jax.tree_util.tree_leaves(state_datas)))
        return full, partial

    # -- the program -------------------------------------------------------
    def _build(self, all_params, train_pos, nd_args=None, states=None):
        """Trace loss-forward + backward + the optimizer update for ALL
        parameters into one pure function and jit it with weight and
        optimizer-state buffers donated (off-CPU; donation is a no-op on
        the host backend).

        Mesh modes (``nd_args``/``states`` supply the operand shapes the
        sharding trees need):

        - dp-only (``_gspmd_mode()`` False): the body is ``shard_map``-ped
          over 'dp' with the explicit psum bucket markers — byte-identical
          to the pre-3D program.
        - GSPMD (any model axis >1, or explicit rules): ONE ``jax.jit``
          whose ``in_shardings`` place params by the partition rules and
          the batch over dp×sp, and whose ``out_shardings`` pin the new
          weights/optimizer state to EXACTLY the input placements — step
          N's donated outputs are step N+1's inputs with zero resharding
          (the matched-shardings contract). The SPMD partitioner supplies
          every collective; the bucket markers run in their axis-free
          form so the reduction still lands per-bucket, and the chunked
          CE's own ``shard_map`` (``parallel/compat.py``) nests inside.
        """
        if _faultpoint.ACTIVE:
            # trace-site fault seam: _dispatch wraps _build in the
            # fallback:trace-failed try, so a raise here exercises the
            # per-step eager degradation a real trace failure takes
            _faultpoint.check("fused_step.trace")
        opt = self._trainer._optimizer
        gspmd = self._gspmd_mode()
        # manual_dp: the legacy dp-only shard_map treatment (explicit
        # axis, explicit psums); gspmd: plain jit + shardings, the
        # partitioner owns the collectives
        manual_dp = self._mesh is not None and not gspmd
        pure_fwd, aux_params = make_pure_forward(all_params, self._call,
                                                 training=True)
        n_all = len(all_params)
        train_set = set(train_pos)
        fixed_pos = tuple(i for i in range(n_all) if i not in train_set)
        mp = opt.multi_precision
        packed_apply = self._packed_apply_fn(opt, all_params, train_pos)

        # health sentinels (ISSUE 15) share the overlap bucket plan with
        # the mesh-mode reduction markers: dtype-homogeneous segments,
        # so the whole summary is a handful of fused reductions.
        # MXTPU_HEALTH / MXTPU_HEALTH_ACTION are signature tokens —
        # flipping either lands on a fresh cache key, never a replay of
        # the other graph.
        plan = None
        hmeta = None
        if self._mesh is not None or _healthmon.enabled():
            from ..parallel import overlap as _overlap
            plan = _overlap.bucket_plan(
                [all_params[pos].data()._data for pos in train_pos],
                self._bucket_bytes)
        if _healthmon.enabled():
            names = [all_params[pos].name for pos in train_pos]
            act = _healthmon.action()
            hmeta = {
                "plan": [list(b) for b in plan],
                "names": names,
                "bucket_names": [[names[i] for i in b] for b in plan],
                "action": act,
                # skip_step discards a poisoned update IN-GRAPH (the
                # only donation-safe place: once the program ran, the
                # old buffers are gone off-CPU); halt gets the same
                # select so a caught HealthHaltError leaves clean
                # weights behind
                "select": act in ("skip_step", "halt"),
                # digests are published for cross-rank SDC comparison
                # only when this program's grads are bitwise-shared
                # across ranks (the mesh-DP psum) — a local digest
                # would false-diverge every healthy step. Under GSPMD
                # rule-sharded params carry SHARDED grads, so digests
                # stay local there.
                "replicated": self._dp > 1 and not gspmd,
            }

        tag = None
        if self._mesh is not None:
            # mesh mode: bucket markers between the grad variables and
            # their use — each bucket's psum over 'dp' fires in the
            # backward the moment its segment completes, hiding the
            # reduction under the rest of the backward (overlap.py).
            # GSPMD form: axis_name=None — the markers keep the flat
            # per-bucket wire batching, the partitioner supplies the
            # reduction itself.
            from ..parallel import overlap as _overlap
            _tag_axis = "dp" if manual_dp else None

            def tag(tds):
                return tuple(_overlap.tag_gradient_buckets(
                    list(tds), _tag_axis, plan=plan, op="sum"))

        def pure_step(train_datas, state_datas, fixed_datas, in_datas,
                      lrs, wds, rescale, rng, corrupt=None):
            if manual_dp:
                # per-shard rng: a replicated key would hand every 'dp'
                # shard identical dropout masks (sample j of shard 0 and
                # shard 1 sharing a mask), shrinking the effective
                # randomness by the dp factor. The GSPMD program traces
                # GLOBALLY (no manual axis), so its one key already
                # draws per-sample masks — and matches the single-device
                # program bitwise.
                rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))

            def loss_of(tds):
                if tag is not None:
                    tds = tag(tds)
                merged = [None] * n_all
                for pos, d in zip(train_pos, tds):
                    merged[pos] = d
                for pos, d in zip(fixed_pos, fixed_datas):
                    merged[pos] = d
                outs, aux = pure_fwd(tuple(merged), in_datas, rng)
                # grad of sum(loss) ≙ backward's all-ones head seed;
                # in mesh mode the local-shard sums psum (via the
                # markers) into the identical full-batch gradient
                return jnp.sum(outs[0]), (outs[0], aux)

            (_, (loss, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_datas)
            if hmeta is not None:
                # the health.grad.corrupt chaos seam: an exact
                # multiply-by-one identity on clean steps, NaN/inf/
                # bit-flip poison when the faultpoint armed the operand
                # — placed after the (mesh) reduction, so injected
                # corruption models post-reduction SDC
                grads = _healthmon.apply_corruption(grads, corrupt)
            # parity note: against the HYBRIDIZED eager path (backward =
            # vjp of the same jitted forward) this program is bitwise
            # identical; the non-hybridized per-op tape can differ by
            # ~1 ULP because XLA fuses tiny dots differently per context
            new_ws, new_sts = [None] * len(train_datas), \
                [None] * len(train_datas)
            packed_idx = packed_apply(train_datas, state_datas) \
                if packed_apply else []
            if packed_idx:
                # MXTPU_FUSED_APPLY: the packed multi-tensor apply —
                # dtype-homogeneous flat segments, ONE kernel launch
                # per bucket, bitwise-equal to the per-param chain
                # (pallas_kernels/optimizer_apply.py)
                from ..pallas_kernels import optimizer_apply as _oa
                pw, ps = _oa.packed_apply(
                    opt, [train_datas[i] for i in packed_idx],
                    [grads[i] for i in packed_idx],
                    [state_datas[i] for i in packed_idx],
                    [lrs[i] for i in packed_idx],
                    [wds[i] for i in packed_idx], rescale)
                for i, nw, ns in zip(packed_idx, pw, ps):
                    new_ws[i] = nw
                    new_sts[i] = ns
            for i in range(len(train_datas)):
                if new_ws[i] is not None:
                    continue
                w, g, st = train_datas[i], grads[i], state_datas[i]
                lr_i, wd_i, rs_i = lrs[i], wds[i], rescale
                if not (mp and _is_low_precision(w.dtype)) \
                        and w.dtype != jnp.float32:
                    # the eager per-param jit receives WEAK host scalars
                    # that demote to the weight dtype; traced operands
                    # are strong f32 — demote explicitly so fp16/bf16
                    # steps do the same low-precision arithmetic
                    lr_i = lr_i.astype(w.dtype)
                    wd_i = wd_i.astype(w.dtype)
                    rs_i = rs_i.astype(w.dtype)
                nw, ns = opt.step_fn_multi_precision(w, g, st, lr_i, wd_i,
                                                     rs_i)
                new_ws[i] = nw
                new_sts[i] = ns
            if manual_dp:
                # aux (BN moving stats) are per-shard estimates —
                # average them so every replica adopts the same value
                # (GSPMD computes them over the global batch already)
                from jax import lax
                aux = tuple(lax.pmean(a, "dp") for a in aux)
            if hmeta is None:
                return loss, tuple(new_ws), tuple(new_sts), grads, aux
            # health sentinels over the (reduced) grads, the PRE-update
            # weights (their reductions overlap the whole program
            # instead of extending the update's critical path — see
            # graph_summary) and the loss — a few fused sum reductions
            # threaded out as one extra tiny output
            health, ok = _healthmon.graph_summary(
                hmeta["plan"], grads, train_datas, loss,
                axis_name="dp" if manual_dp else None)
            if hmeta["select"]:
                # skip_step/halt: a poisoned update is discarded HERE,
                # where both the old and the new buffers still exist
                # (donation aliases them outside the program) — the
                # select is exact when ok, so the clean path stays
                # bitwise-identical
                new_ws = [jnp.where(ok, nw, w)
                          for nw, w in zip(new_ws, train_datas)]
                new_sts = jax.tree_util.tree_map(
                    lambda ns, s: jnp.where(ok, ns, s),
                    tuple(new_sts), tuple(state_datas))
            return loss, tuple(new_ws), tuple(new_sts), grads, aux, \
                health

        body = pure_step
        if manual_dp:
            from ..parallel.compat import PartitionSpec as P
            from ..parallel.compat import shard_map as _shard_map
            raw_mesh = getattr(self._mesh, "mesh", self._mesh)
            # params/states/hypers replicated, batch sharded on 'dp';
            # grads leave the body already psum'd (the markers), the
            # per-sample loss re-assembles across shards
            in_specs = (P(), P(), P(), P("dp"), P(), P(), P(), P())
            out_specs = (P("dp"), P(), P(), P(), P())
            if hmeta is not None:
                in_specs += (P(),)    # the corruption operand
                out_specs += (P(),)   # the (replicated) health summary
            body = _shard_map(
                pure_step, raw_mesh,
                in_specs=in_specs, out_specs=out_specs,
                check_vma=False)
        # weights + optimizer state; the host backend ignores donation
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        in_shs = None
        if self._mesh is not None:
            in_shs = self._input_shardings(all_params, train_pos,
                                           fixed_pos, nd_args, states,
                                           hmeta is not None, gspmd)
        if gspmd:
            # the matched-shardings contract (out == in for donated
            # weights/optimizer state): the compiled program's weight
            # outputs land EXACTLY where the next step reads them.
            # Grads pin to the weight placements so adoption keeps the
            # layout the next backward consumes. loss/aux/health pin
            # REPLICATED (a tree-prefix sharding covers any rank):
            # bytes are trivial, and a multi-process mesh needs them
            # fully addressable on every rank (NDArray.asnumpy of a
            # cross-process-sharded loss cannot materialize).
            from ..parallel.compat import NamedSharding
            from ..parallel.compat import PartitionSpec as P
            rep = NamedSharding(getattr(self._mesh, "mesh", self._mesh),
                                P())
            out_shs = (rep, in_shs[0], in_shs[1], in_shs[0], rep)
            if hmeta is not None:
                out_shs += (rep,)
            jfn = jax.jit(body, in_shardings=in_shs,
                          out_shardings=out_shs,
                          donate_argnums=donate)
        else:
            jfn = jax.jit(body, donate_argnums=donate) if donate \
                else jax.jit(body)
        # contract facts the program-artifact capture (_record_compile →
        # profiler.record_program, the hlolint feed) needs but the entry
        # tuple doesn't carry: which operands were donated, whether this
        # is the GSPMD/manual-dp program, and which top-level output
        # slots were pinned replicated (loss=0, aux=4, health=5).
        self._build_info = {
            "donate": donate,
            "gspmd": bool(gspmd),
            "manual_dp": bool(manual_dp),
            "replicated_slots":
                ((0, 4, 5) if hmeta is not None else (0, 4))
                if gspmd else (),
        }
        return jfn, aux_params, fixed_pos, hmeta, in_shs

    def _input_shardings(self, all_params, train_pos, fixed_pos, nd_args,
                         states, with_corrupt, gspmd):
        """The operand-placement tree, structured EXACTLY like the
        operands tuple ``_run`` assembles (safe to bake at build time —
        the cache key pins every operand aval). dp-only mode reproduces
        the old placement shim: everything replicated, batch
        'dp'-sharded. GSPMD mode places each parameter by the partition
        rules (``PartitionRules.spec_for`` fits the spec to the shape
        and drops axes that don't divide), gives every optimizer-state
        leaf of weight shape the WEIGHT's placement (moments shard with
        their param) and replicates the rest (scalar counts), and
        shards the batch dim over 'dp' / the sequence dim over 'sp'
        when they divide."""
        from ..parallel.compat import NamedSharding, PartitionSpec as P
        raw_mesh = getattr(self._mesh, "mesh", self._mesh)
        rep = NamedSharding(raw_mesh, P())
        dp = max(int(self._sizes.get("dp", 1)), 1)
        sp = max(int(self._sizes.get("sp", 1)), 1)
        rules = self._resolve_rules() if gspmd else None

        def param_sh(pos):
            if not gspmd:
                return rep
            p = all_params[pos]
            shape = tuple(int(d) for d in p.data().shape)
            return NamedSharding(
                raw_mesh, rules.spec_for(p.name, shape, raw_mesh))

        def data_sh(a):
            if not gspmd:
                return NamedSharding(raw_mesh, P("dp"))
            shape = tuple(int(d) for d in a.shape)
            parts = []
            if shape:
                parts.append("dp" if dp > 1 and shape[0] % dp == 0
                             else None)
            if len(shape) > 1 and np.issubdtype(
                    np.dtype(getattr(a, "dtype", np.float32)),
                    np.integer):
                # dim 1 of an integer batch array is a token/sequence
                # dim — shard it over 'sp' (the chunked-CE loss path
                # consumes it sequence-parallel). Float dim 1 is a
                # FEATURE dim: sharding it would split contractions
                # into partial dots whose reordered sums break bitwise
                # parity with the unsharded program for zero benefit.
                parts.append("sp" if sp > 1 and shape[1] % sp == 0
                             else None)
            return NamedSharding(raw_mesh, P(*parts))

        train_shs = tuple(param_sh(pos) for pos in train_pos)
        state_shs = []
        for i, st in enumerate(states):
            wshape = tuple(int(d)
                           for d in all_params[train_pos[i]].data().shape)
            wsh = train_shs[i]
            state_shs.append(jax.tree_util.tree_map(
                lambda l, _w=wsh, _s=wshape:
                    _w if tuple(getattr(l, "shape", ())) == _s else rep,
                _state_to_data(st)))
        fixed_shs = tuple(param_sh(pos) for pos in fixed_pos)
        in_data_shs = tuple(data_sh(a) for a in nd_args)
        shs = (train_shs, tuple(state_shs), fixed_shs, in_data_shs,
               rep, rep, rep, rep)
        if with_corrupt:
            shs += (rep,)
        return shs

    def _packed_apply_fn(self, opt, all_params, train_pos):
        """The MXTPU_FUSED_APPLY eligibility selector, or None when the
        packed multi-tensor apply is off or the optimizer's step math
        is not packable (``Optimizer.fused_apply_supported``). The
        selector runs at trace time over the operand trees and returns
        the positions whose update goes through ``packed_apply`` —
        everything static (dtypes, state structure), so the decision
        bakes into the compiled program and the env toggle is part of
        the cache signature."""
        from ..pallas_kernels import optimizer_apply as _oa
        if not (_oa.enabled() and opt.fused_apply_supported()):
            return None
        mp = opt.multi_precision

        def select(train_datas, state_datas):
            idx, ref_struct = [], None
            for k, d in enumerate(train_datas):
                if mp and _is_low_precision(d.dtype):
                    continue  # (master, base) state: per-param path
                leaves = jax.tree_util.tree_leaves(state_datas[k])
                if any(l.shape != d.shape or l.dtype != d.dtype
                       for l in leaves):
                    continue
                struct = jax.tree_util.tree_structure(state_datas[k])
                if ref_struct is None:
                    ref_struct = struct
                elif struct != ref_struct:
                    continue
                idx.append(k)
            return idx
        return select

    @staticmethod
    def _place_operand(a, sh):
        """Move one operand onto its slot in the mesh placement tree.
        Already-placed arrays (every adopted output after step one, by
        the matched-shardings contract) pass through untouched. A
        single-process mesh takes the ``device_put`` fast path; a
        MULTI-PROCESS mesh is not addressable from one rank, so the
        global array is assembled shard-by-shard from this process's
        full local copy (every operand on this path is process-
        identical: params/state from the deterministic eager warmup,
        the full batch from the loader, host hyperparameter scalars)."""
        if getattr(a, "sharding", None) == sh:
            return a
        if getattr(sh, "is_fully_addressable", True):
            # mxlint: disable=MX018 (mesh re-placement of ALREADY-LEDGERED operands: the post-step adoption (_adopt_fused/_adopt_state) re-registers every surviving buffer; the replaced single-device ones retire via weakref death)
            return jax.device_put(a, sh)
        host = np.asarray(a)
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx: host[idx])

    def _record_compile(self, key, dur_us, cost, hlo, mem, all_params,
                        train_pos, states=None, compiled=None):
        """Feed the compile-attribution registry (ISSUE 8c): measured
        trace+compile+first-run wall time, the program's cost-analysis
        flops/bytes, its collective payload, and the comm_model's
        modeled compute/comm times — the split that turns "step is
        slow" into "DCN all-reduce grew 40%". ``mem`` (ISSUE 13b) is
        the executable's ``memory_analysis()`` dict: its
        argument+output+temp total is the modeled HBM peak behind the
        ``memory.headroom`` gauge and the ``dumps()`` Memory table."""
        flops = bytes_acc = comm_bytes = comp_us = comm_us = None
        dtype = peak = None
        if cost:
            flops = float(cost.get("flops", 0.0)) or None
            bytes_acc = float(cost.get("bytes accessed", 0.0)) or None
        cm = _load_comm_model()
        if cm is not None:
            if hlo is not None:
                try:
                    comm_bytes = cm.collect_hlo_inventory(
                        hlo)["total_bytes"] or None
                except Exception:
                    comm_bytes = None
            if comm_bytes is None and self._dp > 1:
                # mesh mode without an inspectable HLO: the gradient
                # all-reduce payload is analytic — 4 bytes per trainable
                # f32 param (SCALING_r05's validated model)
                comm_bytes = 4 * sum(
                    int(all_params[pos].data().size)
                    for pos in train_pos)
            if flops:
                # the peak is keyed by the program's DOMINANT dtype
                # (by trainable-param bytes): an f32 net runs the MXU
                # at half the bf16 rate, an int8 one (the PR 9
                # quantized-matmul path) at double — a hardcoded bf16
                # peak halved/doubled every modeled compute time and
                # every MFU derived from it (ISSUE 17 satellite)
                dtype = self._dominant_dtype(all_params, train_pos)
                peak = cm.peak_tflops(dtype)
                comp_us = flops / (peak * 1e12) * 1e6
            if comm_bytes:
                comm_us = sum(cm.allreduce_seconds(
                    comm_bytes, max(self._dp, 2))) * 1e6 \
                    if self._dp > 1 else 0.0
        peak_bytes = None
        if mem is not None:
            # modeled resident peak while the program runs: live
            # arguments + outputs + XLA temp arena, minus the aliased
            # bytes — under donation (donate_argnums=(0,1) off-CPU) the
            # weight/opt-state outputs REUSE the argument buffers, and
            # memory_analysis counts those bytes on both sides with
            # alias_size recording the overlap. Generated code is
            # reported separately and lives outside HBM data space.
            peak_bytes = (mem.get("argument_bytes", 0)
                          + mem.get("output_bytes", 0)
                          + mem.get("temp_bytes", 0)
                          - mem.get("alias_bytes", 0))
            mem = dict(mem, peak_bytes=peak_bytes)
            _storage.note_modeled_peak("fused_step", peak_bytes)
        # the registry key must be STABLE across processes (ISSUE 17:
        # tools/perf_report.py --compare joins runs by signature tag):
        # crc32 of the signature tuple's repr, not the seed-randomized
        # builtin hash(). Avals, token strings and static-key entries
        # all repr deterministically.
        keyhash = "%08x" % (zlib.crc32(
            repr(key).encode("utf-8")) & 0xFFFFFFFF)
        self._attr_models.pop(key, None)
        if comp_us is not None or peak_bytes is not None:
            self._attr_models[key] = {
                "compute_us": comp_us or 0.0,
                "comm_us": comm_us or 0.0,
                "device_us": (comp_us or 0.0) + (comm_us or 0.0),
                "peak_bytes": peak_bytes,
                # the tag cache hits thread through watchdog.step_end:
                # same "name:key" string perfmodel derives from the
                # record_compile call below, so the roofline join's
                # two sides meet exactly
                "sig": "fused_step:%s" % keyhash,
            }
        _profiler.record_compile(
            "fused_step", key=keyhash,
            dur_us=dur_us, flops=flops, bytes_accessed=bytes_acc,
            comm_bytes=comm_bytes, modeled_compute_us=comp_us,
            modeled_comm_us=comm_us, memory=mem,
            args={"params": len(train_pos), "dp": self._dp,
                  "dtype": dtype, "peak_tflops": peak})
        if compiled is not None and _compile_cache.enabled() \
                and not self._aot_from_cache:
            # persist the executable for the NEXT process (ISSUE 19b);
            # skip when it just came off disk — re-serializing the same
            # entry buys nothing. store() is best-effort and counts its
            # own failures; a lost entry costs one recompile, never the
            # step.
            _compile_cache.store(key, compiled)
        if hlo is not None:
            # artifact capture (ISSUE 18): hand the HLO plus the
            # contract facts hlolint's H-rules check to the profiler's
            # program store. Everything is extracted EAGERLY into plain
            # Python so no record ever pins the executable.
            try:
                self._capture_program(keyhash, hlo, all_params,
                                      train_pos, states, compiled)
            except Exception:
                _STATS["attr_errors"] += 1

    def _capture_program(self, keyhash, hlo, all_params, train_pos,
                         states, compiled):
        """Build the hlolint program-meta dict for one compiled step and
        feed ``profiler.record_program``. The meta keys are the contract
        (tools/hlolint/capture.py documents them): ``donated`` — flat
        entry-parameter numbers that must appear in the input-output
        alias map (H001); ``plan`` — analytic per-kind collective bytes
        (H002, the same 4-bytes-per-trainable-param model the
        BENCH_MODEL=gspmd_step gate validated at <1%% wire error);
        ``replicated_slots``/``out_specs`` — top-level output slots
        pinned ``P()`` and the specs the executable actually carries
        (H003); ``dtype`` — the dominant param dtype keying the bf16
        upcast rule (H004)."""
        info = self._build_info or {}
        donated = ()
        if info.get("donate"):
            # donate_argnums=(0, 1) donates the train_datas and
            # state_datas tuples; their leaves are the leading entry
            # parameters of the flattened program, in order
            n_donated = len(train_pos)
            if states is not None:
                n_donated += len(jax.tree_util.tree_leaves(
                    [_state_to_data(s) for s in states]))
            donated = tuple(range(n_donated))
        plan = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
                "collective-permute": 0, "all-to-all": 0}
        if self._mesh is not None and self._mesh_n > 1:
            plan["all-reduce"] = 4 * sum(
                int(all_params[pos].data().size) for pos in train_pos)
        out_specs = None
        if compiled is not None:
            try:
                out_specs = [
                    [tuple(getattr(sh, "spec", None) or ())
                     for sh in jax.tree_util.tree_leaves(slot)]
                    for slot in compiled.output_shardings]
            except Exception:
                out_specs = None
        _profiler.record_program(
            "fused_step", "fused_step:%s" % keyhash, hlo,
            meta={"donated": donated,
                  "plan": plan,
                  "replicated_slots":
                      tuple(info.get("replicated_slots", ())),
                  "out_specs": out_specs,
                  "dtype": self._dominant_dtype(all_params, train_pos),
                  "mesh": dict(self._sizes),
                  "gspmd": bool(info.get("gspmd"))})

    @staticmethod
    def _dominant_dtype(all_params, train_pos):
        """Short dtype key (``bf16``/``f32``/``int8``/...) of the
        dtype holding the majority of trainable-param bytes — what the
        program's matmuls actually run in, hence which MXU peak the
        modeled compute time must price against."""
        by_dtype = {}
        for pos in train_pos:
            d = all_params[pos].data()
            name = str(getattr(d, "dtype", None) or "float32")
            size = int(getattr(d, "size", 0))
            item = int(getattr(getattr(d, "dtype", None),
                               "itemsize", 4) or 4)
            by_dtype[name] = by_dtype.get(name, 0) + size * item
        if not by_dtype:
            return "bf16"
        dom = max(by_dtype, key=by_dtype.get)
        return {"float32": "f32", "bfloat16": "bf16",
                "float16": "f16", "int8": "int8",
                "float64": "f32"}.get(dom, "bf16")

    def _run(self, entry, all_params, train_pos, indices, states, nd_args,
             batch_size, aot=False):
        """Execute one fused step: host hyperparameter math (identical to
        the eager update()'s), the compiled program, then pending-result
        adoption back into Parameter.data()/grad() and the state store.
        With ``aot=True`` (the compile step) the program is lowered and
        compiled ahead-of-time so its ``cost_analysis()`` (flops/bytes)
        and optimized HLO feed the attribution registry; the compiled
        executable is kept (``self._aot``) and runs this step."""
        jfn, aux_params, fixed_pos, hmeta, in_shs = entry
        tr = self._trainer
        opt = tr._optimizer
        rescale = tr._scale / batch_size
        tr._check_and_rescale_grad(rescale)
        # count bookkeeping first, exactly like update(); snapshot so a
        # failing run (which then falls back to eager) can't double-count
        prev_num = opt.num_update
        prev_counts = {i: opt._index_update_count.get(i) for i in indices}
        opt._update_count(list(indices))

        def _rollback_counts():
            opt.num_update = prev_num
            for i, c in prev_counts.items():
                if c is None:
                    opt._index_update_count.pop(i, None)
                else:
                    opt._index_update_count[i] = c
        try:
            lrs = [opt.step_lr(i) for i in indices]
            wds = opt._get_wds(list(indices))
            train_params = [all_params[pos] for pos in train_pos]
            train_datas = tuple(p.data()._data for p in train_params)
            state_datas = tuple(_state_to_data(s) for s in states)
            fixed_datas = tuple(all_params[pos].data()._data
                                for pos in fixed_pos)
            in_datas = tuple(a._data for a in nd_args)
            # f32 operands: the framework canonicalizes float64 away at
            # the NDArray boundary (jax x64 stays off), so f32 is full
            # precision for every reachable weight dtype
            operands = (train_datas, state_datas, fixed_datas, in_datas,
                        jnp.asarray(lrs, jnp.float32),
                        jnp.asarray(wds, jnp.float32),
                        jnp.float32(rescale), _random.next_key())
            if hmeta is not None:
                # the health.grad.corrupt chaos operand: 0.0 on clean
                # steps (an exact in-graph multiply-by-one identity)
                operands = operands + (
                    jnp.float32(_healthmon.corruption_operand()),)
            if in_shs is not None:
                # mesh-mode placement: the first fused call receives
                # params/state committed to one device (their eager
                # birthplace); the mesh program spans every device, so
                # each operand moves to ITS slot in the placement tree
                # first. After step one the adopted outputs already
                # carry the matched out_shardings and every put is a
                # no-op — that is the zero-resharding contract. Also
                # what keeps AOT valid: the compiled executable demands
                # exactly these input shardings every call.
                operands = jax.tree_util.tree_map(
                    self._place_operand, operands, in_shs)
            runner = jfn
            if aot and hasattr(jfn, "lower"):
                # AOT lower+compile the compile step so the executable's
                # cost_analysis/HLO feed the attribution registry; the
                # cache key pins every operand aval (and mesh mode
                # pre-places operands above), so the executable stays
                # valid for all later hits of this signature. A failure
                # here is a trace failure like any other: it propagates
                # to _dispatch, which records it and falls back.
                #
                # persistent cache first (ISSUE 19b): the key is the
                # full signature _dispatch stashed in self._ckey —
                # avals + signature-token snapshot + mesh
                # fingerprint + optimizer static key — so a disk hit
                # is exactly the executable this trace would have
                # produced, and the trace+XLA compile is skipped
                # entirely. Any load failure was counted by the
                # cache and falls through to a fresh compile.
                self._aot_from_cache = False
                compiled = None
                if _compile_cache.enabled() and self._ckey is not None:
                    compiled = _compile_cache.load(self._ckey)
                    self._aot_from_cache = compiled is not None
                if compiled is None:
                    compiled = jfn.lower(*operands).compile()
                cost = compiled.cost_analysis()
                cost = cost[0] if isinstance(cost, (list, tuple)) \
                    else cost
                try:
                    hlo = compiled.as_text()
                except Exception:
                    hlo = None
                mem = None
                try:
                    # ISSUE 13b: the executable knows its own HBM
                    # footprint — argument/output/temp/generated
                    # bytes feed the compile registry's Memory
                    # table and the headroom gauge
                    ma = compiled.memory_analysis()
                    mem = {
                        "argument_bytes":
                            int(ma.argument_size_in_bytes),
                        "output_bytes":
                            int(ma.output_size_in_bytes),
                        "temp_bytes": int(ma.temp_size_in_bytes),
                        "alias_bytes":
                            int(ma.alias_size_in_bytes),
                        "generated_code_bytes":
                            int(ma.generated_code_size_in_bytes),
                    }
                except Exception:
                    mem = None  # backend without memory_analysis
                self._aot = (compiled, cost, hlo, mem)
                if self._mesh is not None:
                    # the bench gspmd_step gate and the matched-
                    # shardings check read the most recent program
                    self._last_compiled = compiled
                    self._last_hlo = hlo
                runner = compiled
            if hmeta is not None:
                loss_data, new_ws, new_sts, grads, aux_datas, health = \
                    runner(*operands)
            else:
                loss_data, new_ws, new_sts, grads, aux_datas = \
                    runner(*operands)
        except BaseException:
            _rollback_counts()
            raise
        verdict = None
        if hmeta is not None:
            # the per-step sentinel check runs OUTSIDE the rollback
            # try: the program already committed (donated inputs are
            # gone off-CPU), so a raising telemetry path — a buggy
            # Monitor stat_func, a torn device_get — must neither skip
            # the adoption below nor take the training step down; it is
            # swallowed and counted. A halt verdict is RETURNED, never
            # raised here — adoption must run first (the selected
            # clean outputs are the only valid weights left).
            try:
                verdict = _healthmon.note_step(health, hmeta, grads,
                                               new_ws, batch_size)
            except Exception:
                _STATS["health_errors"] += 1
        halt = verdict.get("halt") if verdict else None
        skipped = bool(verdict and verdict.get("skipped")) \
            or halt is not None
        if skipped:
            # the poisoned update was discarded in-graph: host
            # bookkeeping follows, so the step bitwise never happened
            # (lr schedules keyed on num_update stay aligned with a run
            # that never saw the poisoned step)
            _rollback_counts()
        # pending-result adoption: weights + raw grads into the params,
        # state leaves into the updater's store, aux (moving stats) last
        # (under skip/halt the selected outputs ARE the old weight/state
        # values; the poisoned grads still adopt — next step's
        # post-mortem evidence, overwritten by the next backward)
        for p, nw, g in zip(train_params, new_ws, grads):
            p._adopt_fused(nw, g)
        for st, ns in zip(states, new_sts):
            _adopt_state(st, ns)
        if not skipped:
            for p, a in zip(aux_params, aux_datas):
                tgt = p.data()
                tgt._data = a if a.dtype == tgt.dtype \
                    else a.astype(tgt.dtype)
        if halt is not None:
            # adopt-then-raise: params/state now hold the clean
            # selected buffers on every backend, counts rolled back
            raise halt
        return NDArray(loss_data)

    # -- eager fallback ----------------------------------------------------
    def _call(self, *nd_args):
        if self._block is not None:
            if len(nd_args) >= 2:
                out = self._block(*nd_args[:-1])
                return self._loss_fn(out, nd_args[-1])
            return self._loss_fn(self._block(*nd_args))
        return self._loss_fn(*nd_args)

    def _unplace_mesh(self):
        """A mesh-fused step leaves params/grads/optimizer state
        replicated across the mesh; the eager path runs single-device
        programs, and mixing both commitments is a jit device error.
        Gather everything back to the default device before an eager
        step (rare: warming, indivisible batch, trace failure)."""
        dev = jax.devices()[0]

        def pull(a, tag):
            if a is None:
                return None
            sh = getattr(a, "sharding", None)
            if sh is not None and len(getattr(sh, "device_set", ())) > 1:
                gathered = jax.device_put(a, dev)
                # the gathered single-device buffer replaces a ledgered
                # one (which retires via weakref death) — re-register
                # under the same tag so unplacing never loses bytes
                _storage.ledger_register(gathered, tag,
                                         site="fused_step.unplace")
                return gathered
            return a

        def pull_nd(nd_, tag):
            if nd_ is not None and getattr(nd_, "_data", None) is not None:
                nd_._data = pull(nd_._data, tag)

        params = self._param_split()[0] if self._block is not None \
            else list(self._trainer._params)
        for p in params:
            pull_nd(p._data, "param")
            pull_nd(getattr(p, "_grad", None), "grad")
        upd = getattr(self._trainer, "_updater", None)
        if upd is not None:
            for st in upd.states.values():
                for leaf in jax.tree_util.tree_leaves(
                        st, is_leaf=lambda x: hasattr(x, "_data")):
                    pull_nd(leaf if hasattr(leaf, "_data") else None,
                            "opt_state")

    def _eager_step(self, nd_args, batch_size, ignore_stale_grad):
        """The untraced truth: record, backward, Trainer.step — used for
        warming runs and every fallback, so a fused-ineligible step is
        never a crash, just the eager cost."""
        if self._mesh is not None:
            self._unplace_mesh()
        with autograd.record():
            loss = self._call(*nd_args)
        if not isinstance(loss, NDArray):
            raise TypeError("loss_fn must return one NDArray loss, got %r"
                            % type(loss))
        autograd.backward([loss])
        self._trainer.step(batch_size, ignore_stale_grad=ignore_stale_grad)
        return loss

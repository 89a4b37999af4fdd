"""Generate NDArray-level op wrappers from the functional registry.

Analog of the reference's import-time op wrapper generation
(ref: python/mxnet/ndarray/register.py, python/mxnet/_ctypes/ndarray.py
_imperative_invoke) and of Imperative::Invoke's dispatch
(ref: src/imperative/imperative.cc:89). Each call:

1. unwraps NDArray args to jax arrays,
2. threads PRNG keys / train-mode flags for ops that need them,
3. runs the pure function (XLA async-dispatches — the engine analog),
4. if autograd is recording and the outputs are differentiable, captures the
   ``jax.vjp`` closure on the tape (Imperative::RecordOp analog).

Imperative fast path (``MXNET_IMPERATIVE_JIT=1``, default on):

* **Jitted dispatch cache** — step 3 executes through a ``jax.jit``-compiled
  callable cached per (op name, static attr signature, input avals,
  AMP version), so repeated eager calls hit XLA's executable cache instead
  of dispatching primitive-by-primitive. A key is only compiled once it
  repeats (one-shot shapes stay on the eager path), mirroring how the
  reference only pays CachedOp setup for graphs that are reused. Under
  ``autograd.record()`` the jitted callable is the function ``jax.vjp``
  captures, so gradients flow through the compiled forward. Ops the
  registry marks in-place (``OpDef.inplace``, the ``req='write'`` analog)
  donate those input buffers to XLA on non-CPU backends. Unjittable ops
  (``OpDef.nojit``: host callbacks, data-dependent shapes) and calls whose
  attrs aren't hashable fall back to the untraced path.
* **Bulk segments** — inside ``engine.bulk(n)`` eligible ops are queued
  into a lazy segment and flushed as ONE jitted program at a sync point
  (``.asnumpy()``/buffer read, ``wait_for_var``/``wait_for_all``, autograd
  entry, or segment-full). This is the imperative CachedOp/bulking seam
  (ref: MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN, graph_executor.cc:1288
  InitOpSegs) applied to the eager layer.
"""
from __future__ import annotations

import inspect
import os
import threading
import time as _time
from weakref import ref as _wref

import jax
import numpy as _np

from .. import autograd
from .. import engine as _engine
from ..base import getenv as _getenv
from .. import profiler as _profiler
from .. import random as _random
from .. import storage as _storage
from .._debug import faultpoint as _faultpoint
from .._debug import flightrec as _flightrec
from .._debug import locktrace as _locktrace
from ..ops import registry as _registry
from .ndarray import NDArray, _PendingSlot

__all__ = ["invoke", "invoke_by_name", "make_op_func", "populate",
           "invoke_getitem", "imperative_jit_enabled", "set_imperative_jit",
           "dispatch_stats", "reset_dispatch_stats", "flush_bulk_segment",
           "bulk_segment_depth", "set_profiler_hooks", "aval",
           "register_signature_token", "signature_tokens",
           "signature_token_names"]

# Telemetry hooks at the dispatch choke points (the engine OprBlock hook
# analog, src/profiler/profiler.h:251). The per-op guard is the SHARED
# `_HOOKS and _profiler._LIVE` truth test: _LIVE covers both an active
# profile run and the always-on flight recorder (ISSUE 8) with ONE
# branch — when both are off the entire cost is two truth tests per op
# (BENCH_MODEL=profiler_overhead gates that at <2% of eager dispatch);
# with only the flight recorder on, the extra work is one bare-name
# ring append, no clock read (BENCH_MODEL=flightrec_overhead gates it
# at <0.5%).
# MXNET_PROFILER_HOOKS=0 removes even that (bench baseline / paranoia).
_HOOKS = _getenv("MXNET_PROFILER_HOOKS", "1") \
    not in ("0", "false", "off")

# Sentinel the shared guard yields when ONLY the flight recorder is on
# (_LIVE true, _ACTIVE false): the return sites discriminate on
# identity — `_prof_t0 is _FREC` → bare-name ring breadcrumb, any float
# → full profiler record. No clock read on the flightrec-only path.
_FREC = object()

# Allocation-ledger hot alias (ISSUE 13a): the bound deque.append for
# the 'activation' tag. The per-op registration is ONE
# `(weakref.ref(buf), op_name)` append — no callback, no nbytes read,
# no lock; liveness/size/total bookkeeping all happens at drain time on
# the memwatch/sampler daemons (storage.ledger_metrics). Sits inside
# the shared `_prof_t0 is not None` guard so the off path pays nothing;
# BENCH_MODEL=memory_overhead gates the pair at <0.5% of dispatch.
_LEDGER_ACT = _storage.pending_append("activation")


def set_profiler_hooks(enabled):
    """Toggle the profiler instrumentation guards at runtime (the env var
    ``MXNET_PROFILER_HOOKS`` sets the process default). Returns the
    previous value."""
    global _HOOKS
    prev = _HOOKS
    _HOOKS = bool(enabled)
    return prev

_SPEC_CACHE = {}  # mxlint: disable=MX003 (GIL-atomic op on the dispatch hot path: a lock would cost more than the benign lost-update race; counters are best-effort, caches memoize deterministic values)


def _spec(opdef):
    sp = _SPEC_CACHE.get(opdef.name)
    if sp is None:
        params = inspect.signature(opdef.fn).parameters
        sp = {
            "has_key": "key" in params,
            "has_training": "_training" in params,
        }
        _SPEC_CACHE[opdef.name] = sp
    return sp


from ..base import is_inexact_dtype as _is_inexact  # noqa: E402


# AMP input-cast hook (ref: python/mxnet/contrib/amp/amp.py:251 init —
# the reference rewrites every generated op wrapper at init; here one hook
# at the single dispatch choke point does the same job).
# Signature: hook(op_name, args, kwargs) -> (args, kwargs)
_amp_cast_hook = None
# bumped on every hook change; HybridBlock mixes it into its compile-cache
# key so graphs traced before amp.init() are not silently reused after,
# and the imperative dispatch cache keys on it for the same reason
_amp_version = 0


def set_amp_cast_hook(hook):
    global _amp_cast_hook, _amp_version
    _amp_cast_hook = hook
    _amp_version += 1


# ---------------------------------------------------------------------------
# Jitted dispatch cache (fast path piece 1).
# ---------------------------------------------------------------------------

_JIT_ENABLED = _getenv("MXNET_IMPERATIVE_JIT", "1") \
    not in ("0", "false", "off")
# compile a key only once it repeats: one-shot (op, attrs, avals) combos —
# the norm in test sweeps — stay eager instead of paying a trace+compile
_JIT_THRESHOLD = 2
# full-clear bound so pathological shape churn can't grow without limit
# (the reference bounds CachedOp caches the same blunt way)
_CACHE_CAP = 8192

# mxlint: disable=MX003 (GIL-atomic memo of deterministic jitted callables; worst case a duplicate trace, never a wrong result)
_DISPATCH_CACHE = {}     # full key -> jitted callable
_KEY_COUNTS = {}         # full key -> times seen (for the hot threshold)  # mxlint: disable=MX003 (GIL-atomic heuristic counter: a lost update only delays compile-on-repeat by one call)
_PARTIAL_KEYS = set()    # (name, statics, amp) seen — retrace detection  # mxlint: disable=MX003 (GIL-atomic membership adds; retrace stat is best-effort)
_FAILED_KEYS = set()     # keys that raised under trace — permanent fallback  # mxlint: disable=MX003 (GIL-atomic adds; a racing miss just retries the trace once)

# observability (satellite: profiler counters; included in profiler.dumps)
# mxlint: disable=MX003 (GIL-atomic best-effort counters on the per-op hot path; the <2% overhead gate forbids a lock here)
_STATS = {
    "hits": 0,          # dispatch served by a cached jitted callable
    "misses": 0,        # key not yet compiled (eager while warming, or
                        # compiled this call)
    "retraces": 0,      # compile for an (op, attrs) seen before with
                        # different avals — shape/dtype churn indicator
    "fallbacks": 0,     # fast path enabled but call took the untraced path
    "bulk_flushes": 0,  # bulk segments executed as one program
    "bulk_ops": 0,      # ops that executed inside a bulk segment
    "bulk_fallbacks": 0,  # segment runners that raised and replayed
                          # eagerly (the 'eager-fallback' flush mode)
}


def imperative_jit_enabled():
    return _JIT_ENABLED


def set_imperative_jit(enabled):
    """Toggle the imperative fast path at runtime (the env var
    ``MXNET_IMPERATIVE_JIT`` sets the process default). Returns the
    previous value."""
    global _JIT_ENABLED
    prev = _JIT_ENABLED
    _JIT_ENABLED = bool(enabled)
    return prev


def dispatch_stats():
    """Snapshot of the dispatch-cache counters (hits/misses/retraces/
    fallbacks/bulk_flushes/bulk_ops)."""
    return dict(_STATS)


def reset_dispatch_stats():
    for k in _STATS:
        _STATS[k] = 0


def _clear_dispatch_cache():
    _DISPATCH_CACHE.clear()
    _KEY_COUNTS.clear()
    _PARTIAL_KEYS.clear()
    _FAILED_KEYS.clear()
    _AVAL_CACHE.clear()


_UNHASHABLE = object()


def _canon(v):
    """Canonicalize a static attr value into something hashable, or
    _UNHASHABLE to force the untraced path."""
    if v is None or isinstance(v, (str, bytes)):
        return v
    if isinstance(v, (bool, int, float, complex)):
        # the class is part of the key: 2 == 2.0 == True hash-collide, but
        # an int-2 closure and a float-2.0 closure promote dtypes
        # differently — replaying one for the other is silently wrong
        return (v.__class__, v)
    if isinstance(v, (list, tuple)):
        out = tuple(_canon(x) for x in v)
        return _UNHASHABLE if _UNHASHABLE in out else out
    if isinstance(v, dict):
        items = tuple(sorted((str(k), _canon(x)) for k, x in v.items()))
        return _UNHASHABLE if any(x is _UNHASHABLE for _, x in items) \
            else items
    if isinstance(v, _np.dtype):
        return str(v)
    if isinstance(v, _np.generic):
        return (str(v.dtype), v.item())
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        # array-like (NDArray/jax/np inside an attr): identity-hashable,
        # but its buffer can be rebound after the closure bakes it as a
        # constant — never cache on it
        return _UNHASHABLE
    try:
        hash(v)
    except TypeError:
        return _UNHASHABLE
    return v


def _aval(d):
    # np.dtype objects hash/compare by identity semantics and are cheap
    # key components; str(dtype) costs ~10us and is avoided on purpose
    return (d.shape, d.dtype, getattr(d, "weak_type", False))


def aval(d):
    """Hashable signature component for one jax array: (shape, dtype,
    weak_type). The shared key ingredient of every signature-keyed
    compile-on-repeat cache in the tree — the dispatch cache and bulk
    segments here, and the gluon fused train step
    (gluon/fused_step.py) — so they all discriminate inputs the same
    way."""
    return _aval(d)


def _snapshot(v):
    """Copy mutable attr containers so a queued bulk op is immune to the
    caller mutating them between queue and flush (the cache key was taken
    at queue time; the traced closure must see the same values)."""
    if isinstance(v, list):
        return [_snapshot(x) for x in v]
    if isinstance(v, tuple):
        return tuple(_snapshot(x) for x in v)
    if isinstance(v, dict):
        return {k: _snapshot(x) for k, x in v.items()}
    return v


def _build_traced(opdef, args, kwargs, arg_slots, kw_slots, take_key):
    """Build the pure positional-array function the jit/vjp machinery
    consumes. Statics are baked from THIS call (sound: the cache key pins
    them); NDArray slots are stripped so the cached closure never pins
    first-call buffers."""
    slot_set = set(arg_slots)
    s_args = [None if i in slot_set else a for i, a in enumerate(args)]
    kw_set = set(kw_slots)
    s_kwargs = {k: (None if (k in kw_set or (take_key and k == "key"))
                    else v) for k, v in kwargs.items()}
    n_args = len(arg_slots)
    n_kw = len(kw_slots)
    fn = opdef.fn

    def traced(*xs):
        new_args = list(s_args)
        new_kwargs = dict(s_kwargs)
        for slot, x in zip(arg_slots, xs[:n_args]):
            new_args[slot] = x
        for k, x in zip(kw_slots, xs[n_args:n_args + n_kw]):
            new_kwargs[k] = x
        if take_key:
            new_kwargs["key"] = xs[-1]
        return fn(*new_args, **new_kwargs)

    return traced


def _donate_argnums(opdef, arg_slots, recording):
    """Map OpDef.inplace (positional tensor-input indices) onto positions
    in the traced-arg tuple. Donation is a pure buffer-reuse hint to XLA:
    only meaningful off-CPU, never while recording (residuals alias
    inputs)."""
    if not opdef.inplace or recording:
        return ()
    try:
        if jax.default_backend() == "cpu":
            return ()  # donation is a no-op on CPU; skip the warning
    except Exception:
        return ()
    donate = []
    for idx in opdef.inplace:
        try:
            donate.append(arg_slots.index(idx))
        except ValueError:
            pass  # in-place input passed as kwarg/static — skip
    return tuple(donate)


def _cached_callable(opdef, key, partial_key, args, kwargs, arg_slots,
                     kw_slots, take_key, recording):
    """Return the jitted callable for ``key``, compiling it once the key
    has repeated (_JIT_THRESHOLD), or None while warming."""
    fn = _DISPATCH_CACHE.get(key)
    if fn is not None:
        _STATS["hits"] += 1
        return fn
    _STATS["misses"] += 1
    if len(_KEY_COUNTS) >= 4 * _CACHE_CAP:
        _KEY_COUNTS.clear()  # one-shot keys (shape churn) must not leak
    seen = _KEY_COUNTS.get(key, 0) + 1
    _KEY_COUNTS[key] = seen
    if seen < _JIT_THRESHOLD:
        return None
    if len(_DISPATCH_CACHE) >= _CACHE_CAP:
        _clear_dispatch_cache()
    if partial_key in _PARTIAL_KEYS:
        _STATS["retraces"] += 1
    _PARTIAL_KEYS.add(partial_key)
    traced = _build_traced(opdef, args, kwargs, arg_slots, kw_slots,
                           take_key)
    donate = _donate_argnums(opdef, arg_slots, recording)
    if _locktrace.ENABLED:
        # the first call of this jitted fn traces + compiles (seconds):
        # a framework lock held here starves every other thread
        _locktrace.boundary("imperative.jit_compile")
    if _faultpoint.ACTIVE:
        # compile-site fault seam: a raise here is caught by invoke(),
        # which marks the key permanently failed and dispatches eagerly
        # — the same degradation a real jax.jit construction error takes
        _faultpoint.check("imperative.jit.compile")
    fn = jax.jit(traced, donate_argnums=donate) if donate \
        else jax.jit(traced)
    probe = _compile_probe(opdef, key, fn)
    _DISPATCH_CACHE[key] = probe
    return probe


def _sig_repr(key):
    """Compact human-readable form of a dispatch-cache key's avals for
    the compile-attribution registry (shape churn reads as the same
    name with a changing key)."""
    avals = key[-1]
    try:
        return ",".join("%s%s" % (_np.dtype(dt).name, list(shape))
                        for shape, dt, _w in avals)
    except Exception:
        return repr(avals)[:80]


def _compile_probe(opdef, key, fn):
    """One-shot wrapper timing the FIRST call of a fresh jitted
    callable — trace + XLA compile + first run — into the compile-
    attribution registry (profiler.record_compile, ISSUE 8c), then
    unwraps itself from the dispatch cache so every later hit pays
    nothing. Compiles are rare and expensive: they are recorded
    unconditionally (the ``account`` contract), not only under a
    profile run."""
    def probe(*xs):
        t0 = _time.perf_counter()
        out = fn(*xs)
        if _DISPATCH_CACHE.get(key) is probe:
            _DISPATCH_CACHE[key] = fn
        _profiler.record_compile("imperative:%s" % opdef.name,
                                 key=_sig_repr(key),
                                 dur_us=(_time.perf_counter() - t0) * 1e6)
        return out
    return probe


def _record_invoke(opdef, t0):
    # mxlint: disable=MX002 (called only when _prof_t0 is not None, i.e. under the inlined `_HOOKS and _ACTIVE` guard at both call sites — keeping the guard expression inline there is the whole point)
    _profiler.record_op(opdef.name, (_time.perf_counter() - t0) * 1e6,
                        category="operator", lane="imperative")


def invoke(opdef, args, kwargs):
    # telemetry guard is inlined (no wrapper call) and SHARED between
    # the profiler and the always-on flight recorder (_LIVE, ISSUE 8):
    # with both off the whole cost is this one conditional plus two
    # `is not None` tests at the return sites. With only the flight
    # recorder on, the guard yields the _FREC sentinel instead of a
    # timestamp — no clock read — and the return sites append ONE bare
    # op-name breadcrumb to the ring (dump-time rendering anchors it to
    # the nearest timestamped neighbor). A perf_counter pair alone
    # costs ~3x the flightrec budget per op, which is why the
    # flightrec-only path records order, not durations
    # (BENCH_MODEL=profiler_overhead / flightrec_overhead gate both).
    _prof_t0 = (_time.perf_counter() if _profiler._ACTIVE else _FREC) \
        if (_HOOKS and _profiler._LIVE) else None
    spec = _spec(opdef)
    if _amp_cast_hook is not None or spec["has_key"] or spec["has_training"]:
        kwargs = dict(kwargs)
        if _amp_cast_hook is not None:
            args, kwargs = _amp_cast_hook(opdef.name, args, kwargs)
        if spec["has_key"] and kwargs.get("key") is None:
            kwargs["key"] = _random.next_key()
        if spec["has_training"] and "_training" not in kwargs:
            kwargs["_training"] = autograd.is_training()

    # collect differentiable NDArray inputs from args and kwargs
    arg_slots = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
    if kwargs:
        kw_slots = [k for k, v in kwargs.items()
                    if isinstance(v, NDArray) and k != "key"]
        nd_inputs = [args[i] for i in arg_slots] \
            + [kwargs[k] for k in kw_slots]
    else:
        kw_slots = []
        nd_inputs = [args[i] for i in arg_slots] \
            if len(arg_slots) != len(args) else list(args)

    fast_ok = _JIT_ENABLED and not opdef.nojit
    recording = autograd.is_recording()

    # -- bulk segment (fast path piece 2): queue instead of executing ----
    # (NaiveEngine is checked once at engine.bulk entry, not per op)
    if fast_ok and not recording:
        seg = getattr(_BULK_LOCAL, "segment", None)
        if seg is not None:
            out = seg.try_queue(opdef, spec, args, kwargs, arg_slots,
                                kw_slots, nd_inputs)
            if out is not _NOT_BULKED:
                if _prof_t0 is not None:
                    if _prof_t0 is _FREC:
                        # flight-recorder-only path: bare-name ring
                        # append, inlined — even a helper call or one
                        # clock read would breach the <0.5%-of-dispatch
                        # budget
                        _flightrec.RING.append(opdef.name)
                    else:
                        _record_invoke(opdef, _prof_t0)
                return out

    datas = tuple(a._data for a in nd_inputs)

    recording = (recording and not opdef.no_grad
                 and len(datas) > 0
                 and any(_is_inexact(d.dtype) for d in datas))

    # PRNG key: a per-call jax array. The jitted path must take it as a
    # traced argument — a closure-captured key would be baked into the
    # compiled executable as a constant and every later hit would silently
    # reuse the first call's randomness.
    if spec["has_key"]:
        key_val = kwargs.get("key")
        if isinstance(key_val, NDArray):
            key_val = key_val._data
        take_key = key_val is not None and hasattr(key_val, "dtype")
    else:
        key_val = None
        take_key = False

    jfn = None
    if fast_ok:
        key, partial_key = _dispatch_key(opdef, args, kwargs, arg_slots,
                                         kw_slots, datas, key_val, take_key,
                                         recording)
        if key is not None and key not in _FAILED_KEYS:
            try:
                jfn = _cached_callable(opdef, key, partial_key, args,
                                       kwargs, arg_slots, kw_slots,
                                       take_key, recording)
            except Exception:
                # jax.jit construction failed (bad donation spec, or an
                # injected imperative.jit.compile fault): permanent
                # eager fallback for this key — never a crash. Before
                # this guard a constructor error propagated to the user
                # even though the eager path was perfectly able to run.
                if len(_FAILED_KEYS) >= _CACHE_CAP:
                    _FAILED_KEYS.clear()
                _FAILED_KEYS.add(key)
                _DISPATCH_CACHE.pop(key, None)
                _STATS["fallbacks"] += 1
                jfn = None
        else:
            _STATS["fallbacks"] += 1
    elif _JIT_ENABLED and opdef.nojit:
        _STATS["fallbacks"] += 1  # registry opt-out (host callback etc.)

    fwd = None
    out = _PENDING_SENTINEL
    vjp_fn = None
    if jfn is not None:
        jit_fwd = (lambda *xs: jfn(*xs, key_val)) if take_key else jfn
        try:
            if recording:
                out, vjp_fn = jax.vjp(jit_fwd, *datas)
            else:
                out = jit_fwd(*datas)
        except Exception:
            # trace-incompatible op (concretization, host callback, ...):
            # remember the key and re-run the genuine eager path below so
            # real errors surface from untraced execution
            if len(_FAILED_KEYS) >= _CACHE_CAP:
                _FAILED_KEYS.clear()  # shape churn must not leak keys
            _FAILED_KEYS.add(key)
            _DISPATCH_CACHE.pop(key, None)
            _STATS["fallbacks"] += 1
            out = _PENDING_SENTINEL
        else:
            fwd = jit_fwd  # the tape replays through the compiled forward

    if out is _PENDING_SENTINEL:
        def fwd(*xs):
            new_args = list(args)
            new_kwargs = dict(kwargs)
            for slot, x in zip(arg_slots, xs[:len(arg_slots)]):
                new_args[slot] = x
            for k, x in zip(kw_slots, xs[len(arg_slots):]):
                new_kwargs[k] = x
            return opdef.fn(*new_args, **new_kwargs)

        if recording:
            out, vjp_fn = jax.vjp(fwd, *datas)
        else:
            out = fwd(*datas)

    multi = isinstance(out, (tuple, list))
    raw_outs = list(out) if multi else [out]
    # NaiveEngine forced sync: errors surface at the faulting op
    # (ref: src/engine/naive_engine.cc serial debugging mode)
    if _engine.is_naive():
        _engine.maybe_sync(raw_outs)
    outs = [NDArray(o) for o in raw_outs]

    if recording:
        if all(_is_inexact(o.dtype) for o in raw_outs):
            node = autograd.record_op(opdef.name, outs, nd_inputs, vjp_fn)
            node.fwd_fn = fwd
        # else: non-differentiable output — gradient stops here
    if _prof_t0 is not None:
        if _prof_t0 is _FREC:
            # flight-recorder-only path: see the bulk return site above
            _flightrec.RING.append(opdef.name)
        else:
            _record_invoke(opdef, _prof_t0)
        if _storage._LEDGER_ON:
            # tag every fresh eager result 'activation' in the
            # allocation ledger; the op name doubles as the site label
            for _o in raw_outs:
                _LEDGER_ACT((_wref(_o), opdef.name))
    return tuple(outs) if multi else outs[0]


# ---------------------------------------------------------------------------
# Compile-signature token registry.
#
# Env vars whose VALUE changes a traced graph (Pallas kernel routing,
# the packed optimizer apply) are exactly the ambient state the PR 9
# review pass caught leaking into cached executables: a hot signature
# silently replayed the pre-flip path until the kernel envs joined the
# dispatch key. The registry formalizes that fix — register a var here
# and its current value joins EVERY compile-cache signature (the
# imperative dispatch key below AND gluon/fused_step's program key), so
# flipping it mid-process recompiles instead of replaying stale code.
# mxlint MX014 closes the loop statically: an env read reachable from a
# trace entry point must name a registered token (or carry a waiver).
# ---------------------------------------------------------------------------

# [(name, default)] in registration order
_SIG_TOKENS = []  # mxlint: disable=MX003 (appended at import/plugin-registration time only, which serializes under the import lock / lib_api load lock; key builds only iterate)


def register_signature_token(name, default=""):
    """Register an env var as part of every compile-cache signature.
    Idempotent per name; returns the name so modules can do
    ``_ENV = register_signature_token("MXTPU_X", "1")``."""
    for n, _ in _SIG_TOKENS:
        if n == name:
            return name
    _SIG_TOKENS.append((str(name), str(default)))
    return name


def signature_token_names():
    """Registered token names, registration order (doc/lint surface)."""
    return tuple(n for n, _ in _SIG_TOKENS)


def signature_tokens():
    """Current values of every registered token, as one hashable tuple.
    Both cache-key builders consume this: a handful of dict lookups per
    key build, far below the aval hashing already paid."""
    # mxlint: disable=MX015 (the registry's own read loop: every name here came through register_signature_token, which MX015 doc-checks individually)
    return tuple(_getenv(n, d) for n, d in _SIG_TOKENS)


# The kernel-routing switches (ops/nn.py batch_norm, ops/quantized.py,
# the global kill switch) and the packed-apply toggle that changes
# traced update/kernel graphs. New env-routed kernels register
# theirs alongside these.
register_signature_token("MXTPU_NO_PALLAS", "0")
register_signature_token("MXTPU_FUSED_BN", "1")
register_signature_token("MXTPU_QUANT_MATMUL", "1")
register_signature_token("MXTPU_FUSED_APPLY", "0")
# the packed-apply bucket plan (parallel/overlap.bucket_plan) reads the
# bucket-size cap at trace time, so it shapes the traced update graph —
# found by mxlint MX014 on its first whole-tree run (exactly the PR 9
# stale-replay class: flip the cap mid-run, replay the old bucketing)
register_signature_token("MXTPU_ELASTIC_BUCKET_MB", "4")
# training-health sentinels (ISSUE 15): MXTPU_HEALTH threads the
# summary/corruption operands through the fused-step program, and the
# skip_step/halt actions add the in-graph discard select — both change
# the traced graph, so flipping either must retrace, never replay
register_signature_token("MXTPU_HEALTH", "0")
register_signature_token("MXTPU_HEALTH_ACTION", "record")
# 3D-parallel trainer path (docs/PARALLEL.md): the chunked-CE
# local-accumulation auto-select (parallel/transformer.loss_fn) and the
# fused step's GSPMD mesh mode (gluon/fused_step.py) both branch the
# traced graph on these at trace time — flipping either mid-run must
# land on a fresh cache key, never replay the other program
register_signature_token("MXTPU_CE_LOCAL_ACCUM", "auto")
register_signature_token("MXTPU_GSPMD_STEP", "1")
# zero-badput legs (ISSUE 19): the persistent AOT compile cache keys
# serialized executables by the FULL token-registry snapshot, so every
# switch that gates one of the three legs must itself be a token — a
# cache entry written under one setting can then never be replayed
# under another (the same stale-replay class MX014 polices for traced
# graphs, applied to on-disk executables)
register_signature_token("MXTPU_CKPT_ASYNC", "0")
register_signature_token("MXTPU_CKPT_DELTA", "0")
register_signature_token("MXTPU_COMPILE_CACHE_DIR", "")
register_signature_token("MXTPU_PEER_RESTORE", "0")
# control-plane survivability legs (ISSUE 20): none of these shape a
# traced graph, but each changes what recovery/resume semantics a
# process commits to (journaled vs in-memory server state, fenced vs
# unfenced writes, drain-vs-die on SIGTERM, single vs chained
# endpoints) — a resumed or cache-replayed run must agree with the run
# that wrote its artifacts, so they ride the same registry snapshot the
# ISSUE 19 knobs do
register_signature_token("MXTPU_PS_JOURNAL_DIR", "")
register_signature_token("MXTPU_PS_ENDPOINTS", "")
register_signature_token("MXTPU_PS_FENCING", "0")
register_signature_token("MXTPU_PREEMPT_GRACE_S", "0")

# back-compat spelling (PR 9 introduced the kernel-env tuple under this
# name; the registry supersedes it)
_kernel_env_token = signature_tokens


def _dispatch_key(opdef, args, kwargs, arg_slots, kw_slots, datas, key_val,
                  take_key, recording):
    """(full cache key, partial key) or (None, None) if unhashable."""
    if len(arg_slots) == len(args) and not kwargs:
        statics = ()  # hot case: pure tensor call, no attrs
    else:
        statics = []
        slot_set = set(arg_slots)
        for i, a in enumerate(args):
            if i not in slot_set:
                c = _canon(a)
                if c is _UNHASHABLE:
                    return None, None
                statics.append((i, c))
        kw_set = set(kw_slots)
        for k in sorted(kwargs):
            if k in kw_set or (take_key and k == "key"):
                continue
            c = _canon(kwargs[k])
            if c is _UNHASHABLE:
                return None, None
            statics.append((k, c))
        statics = tuple(statics)
    avals = tuple(_aval(d) for d in datas)
    if take_key:
        avals = avals + (_aval(key_val),)
    partial = (opdef.name, statics, tuple(arg_slots), tuple(kw_slots),
               _amp_version, recording, signature_tokens())
    return partial + (avals,), partial


def invoke_by_name(name, *args, **kwargs):
    return invoke(_registry.get_op(name), args, kwargs)


def _as_data(v):
    return v._data if isinstance(v, NDArray) else v


def invoke_getitem(arr, key):
    """Basic+advanced indexing as a recorded op (differentiable gather)."""

    def fwd(x):
        return x[key]

    if autograd.is_recording() and _is_inexact(arr.dtype):
        out, vjp_fn = jax.vjp(fwd, arr._data)
        res = NDArray(out)
        node = autograd.record_op("getitem", [res], [arr], vjp_fn)
        node.fwd_fn = fwd
        return res
    return NDArray(fwd(arr._data))


# ---------------------------------------------------------------------------
# Bulk segments (fast path piece 2): engine.bulk's lazy op accumulator.
# ---------------------------------------------------------------------------

_PENDING_SENTINEL = object()
_NOT_BULKED = object()
_BULK_LOCAL = threading.local()

# out-aval cache: (name, statics, in avals) -> tuple of (shape, dtype)
_AVAL_CACHE = {}  # mxlint: disable=MX003 (GIL-atomic memo of eval_shape results: deterministic, duplicate compute is the worst case)


def bulk_segment_depth():
    """Number of ops currently queued in this thread's bulk segment."""
    seg = getattr(_BULK_LOCAL, "segment", None)
    return len(seg.ops) if seg is not None else 0


def begin_bulk_segment(limit):
    """Install a fresh bulk segment for this thread (engine.bulk enter).
    Any previously active segment is flushed first, so cross-segment
    dataflow can never arise; it is restored (empty) when this one ends,
    so nested engine.bulk scopes compose."""
    flush_bulk_segment()
    seg = _BulkSegment(max(1, int(limit)))
    seg.prev = getattr(_BULK_LOCAL, "segment", None)
    _BULK_LOCAL.segment = seg
    return seg


def end_bulk_segment(seg=None):
    """Flush and deactivate the current segment (engine.bulk exit). The
    segment is deactivated even if the flush raises — a zombie segment
    would silently keep queueing every later op on this thread."""
    cur = getattr(_BULK_LOCAL, "segment", None)
    try:
        if cur is not None:
            cur.flush()
    finally:
        _BULK_LOCAL.segment = getattr(seg or cur, "prev", None)


def flush_bulk_segment():
    """Drain this thread's pending bulk segment (sync points: wait_for_all,
    wait_for_var, autograd.backward, engine.set_bulk_size)."""
    cur = getattr(_BULK_LOCAL, "segment", None)
    if cur is not None:
        cur.flush()


def set_active_bulk_limit(limit):
    """Apply a mid-scope engine.set_bulk_size to the live segment (the
    flush already happened; future ops must honor the new cap)."""
    cur = getattr(_BULK_LOCAL, "segment", None)
    if cur is not None:
        cur.limit = max(1, int(limit))


# runner cache: segment signature -> jitted program over the leaf arrays
_SEGMENT_CACHE = {}  # mxlint: disable=MX003 (GIL-atomic memo of jitted segment runners, same contract as _DISPATCH_CACHE)
_SEGMENT_COUNTS = {}  # signature -> times flushed (compile-on-repeat)  # mxlint: disable=MX003 (GIL-atomic heuristic counter, see _KEY_COUNTS)


def deliver_result(dst, src):
    """dst NDArray <- src NDArray's value, preserving dst's dtype (the
    out=/state-writeback delivery contract). A still-pending bulk result
    with matching dtype is ADOPTED — dst resolves at the segment flush —
    instead of forcing a per-op flush."""
    rb = src._buf
    if type(rb) is _PendingSlot and dst.dtype == src.dtype \
            and isinstance(rb.segment, _BulkSegment):
        rb.segment.adopt(dst, rb)
        dst._buf = rb
    else:
        d = src._data
        dst._data = d.astype(dst._data.dtype) \
            if d.dtype != dst._data.dtype else d
    return dst


class _BulkSegment:
    """Accumulates eager op thunks; flushes them as ONE jitted XLA program
    (the CachedOp/InitOpSegs analog for the imperative layer)."""

    def __init__(self, limit):
        self.limit = limit
        self.ops = []        # (opdef.name, statics, in_refs, call, multi)
        self.leaves = []     # concrete jax arrays feeding the segment
        self.leaf_ids = {}   # id(jax array) -> leaf index
        self.outs = []       # (ndarray, placeholder, op_idx, out_idx)
        self.prev = None     # outer segment to restore on scope exit

    def adopt(self, arr, slot):
        """Register an extra NDArray to receive ``slot``'s result at flush
        (out= delivery aliasing a still-pending output)."""
        self.outs.append((arr, slot, slot.ref[1], slot.ref[2]))

    def try_queue(self, opdef, spec, args, kwargs, arg_slots, kw_slots,
                  nd_inputs):
        """Queue the op if it is bulkable; _NOT_BULKED otherwise."""
        key_val = kwargs.get("key") if spec["has_key"] else None
        if isinstance(key_val, NDArray):
            key_val = key_val._data
        take_key = key_val is not None and hasattr(key_val, "dtype")

        # statics must be hashable (they key the cached runner)
        key, _partial = _dispatch_key(opdef, args, kwargs, arg_slots,
                                      kw_slots, (), key_val, take_key,
                                      False)
        if key is None or opdef.name in _BULK_FAILED_OPS:
            return _NOT_BULKED
        statics = key[:-1]

        # resolve traced inputs: pending refs from THIS segment chain
        # lazily; anything else becomes a concrete leaf. New leaves are
        # STAGED and only committed once the op is definitely queued —
        # a bail-out must not leave orphan leaves that perturb the
        # segment signature (spurious runner recompiles).
        staged = []       # jax arrays not yet in self.leaves
        staged_ids = {}   # id -> provisional leaf index

        def leaf_ref(buf):
            idx = self.leaf_ids.get(id(buf))
            if idx is None:
                idx = staged_ids.get(id(buf))
                if idx is None:
                    idx = len(self.leaves) + len(staged)
                    staged.append(buf)
                    staged_ids[id(buf)] = idx
            return ("l", idx)

        in_refs = []
        in_avals = []
        bufs = [a._buf for a in nd_inputs]
        for buf in bufs:
            if type(buf) is _PendingSlot:
                if buf.segment is not self:
                    buf.segment.flush()  # foreign segment: materialize
                    return _NOT_BULKED
                in_refs.append(buf.ref)
                in_avals.append((buf.shape, buf.dtype, False))
            else:
                in_refs.append(leaf_ref(buf))
                in_avals.append(_aval(buf))
        if take_key:
            in_refs.append(leaf_ref(key_val))
            in_avals.append(_aval(key_val))

        # attr containers are snapshotted: the runner cache is keyed on
        # their queue-time values, so the flush-time closure must be
        # immune to the caller mutating them in between
        slot_set = set(arg_slots)
        s_args = tuple(a if i in slot_set else _snapshot(a)
                       for i, a in enumerate(args))
        kw_set = set(kw_slots)
        s_kwargs = {k: (v if (k in kw_set or k == "key") else _snapshot(v))
                    for k, v in kwargs.items()}
        # the traced closure itself is built lazily at flush time, only
        # when the segment-runner cache misses
        call = (opdef, s_args, s_kwargs, tuple(arg_slots), tuple(kw_slots),
                take_key)

        # output avals via abstract eval (cached per op+statics+avals)
        aval_key = (opdef.name, statics, tuple(in_avals))
        out_avals = _AVAL_CACHE.get(aval_key)
        if out_avals is None:
            structs = [jax.ShapeDtypeStruct(tuple(shape), _np.dtype(dtype))
                       for shape, dtype, _w in in_avals]
            try:
                shaped = jax.eval_shape(_build_traced(*call), *structs)
            except Exception:
                _BULK_FAILED_OPS.add(opdef.name)
                return _NOT_BULKED
            multi = isinstance(shaped, (tuple, list))
            out_avals = (bool(multi),
                         tuple((tuple(s.shape), s.dtype)
                               for s in (shaped if multi else [shaped])))
            if len(_AVAL_CACHE) >= _CACHE_CAP:
                _AVAL_CACHE.clear()
            _AVAL_CACHE[aval_key] = out_avals

        for buf in staged:
            self.leaf_ids[id(buf)] = len(self.leaves)
            self.leaves.append(buf)

        multi, shapes = out_avals
        op_idx = len(self.ops)
        self.ops.append((opdef.name, statics, tuple(in_refs), call, multi))
        outs = []
        for out_idx, (shape, dtype) in enumerate(shapes):
            slot = _PendingSlot(self, shape, dtype, ("o", op_idx, out_idx))
            arr = NDArray(slot)
            self.outs.append((arr, slot, op_idx, out_idx))
            outs.append(arr)
        _STATS["bulk_ops"] += 1
        if len(self.ops) >= self.limit:
            self.flush()
        return tuple(outs) if multi else outs[0]

    def flush(self):
        """Execute all queued ops as one jitted program and deliver the
        results onto their NDArrays. When profiling is on, the flush is a
        span in the ``bulk`` lane carrying the op count and whether this
        segment compiled, replayed a cached program, or ran eagerly — and
        a memory sample lands at the boundary (allocation churn point)."""
        if not self.ops:
            return
        if _HOOKS and _profiler._LIVE:
            n_ops = len(self.ops)
            t0 = _time.perf_counter()
            mode = self._flush_impl()
            _profiler.record_op(
                "bulk_segment", (_time.perf_counter() - t0) * 1e6,
                category="bulk", lane="bulk",
                args={"ops": n_ops, "mode": mode})
            _profiler.sample_memory("bulk_flush")
        else:
            self._flush_impl()

    def _flush_impl(self):
        """Returns how the segment executed: ``cached`` (jitted runner
        hit), ``compile`` (runner traced+compiled this flush),
        ``eager-warming`` (signature below the compile-on-repeat
        threshold), or ``eager-fallback`` (runner raised; replayed
        untraced)."""
        ops, leaves, outs = self.ops, self.leaves, self.outs
        self.ops, self.leaves, self.outs = [], [], []
        self.leaf_ids = {}

        sig = (tuple((name, statics, in_refs, multi)
                     for name, statics, in_refs, _call, multi in ops),
               tuple(_aval(l) for l in leaves))
        mode = "cached"
        runner = _SEGMENT_CACHE.get(sig)
        if runner is None:
            # compile-on-repeat, like the dispatch cache: a signature seen
            # once (e.g. a per-step lr schedule baking a fresh scalar into
            # every segment) replays eagerly instead of paying a whole-
            # segment trace+compile per flush
            if len(_SEGMENT_COUNTS) >= 4 * _CACHE_CAP:
                _SEGMENT_COUNTS.clear()
            seen = _SEGMENT_COUNTS.get(sig, 0) + 1
            _SEGMENT_COUNTS[sig] = seen
            if seen < _JIT_THRESHOLD:
                self._replay_eager(ops, leaves, outs)
                _STATS["bulk_flushes"] += 1
                return "eager-warming"
            if len(_SEGMENT_CACHE) >= _CACHE_CAP:
                _SEGMENT_CACHE.clear()
            spec = [(_build_traced(*call), in_refs, multi)
                    for _name, _statics, in_refs, call, multi in ops]

            def run(leaf_vals):
                results = []
                for fn, in_refs, multi in spec:
                    ins = [leaf_vals[r[1]] if r[0] == "l"
                           else results[r[1]][r[2]] for r in in_refs]
                    o = fn(*ins)
                    results.append(tuple(o) if multi else (o,))
                return results

            if _locktrace.ENABLED:
                _locktrace.boundary("imperative.bulk_compile")
            runner = jax.jit(run)
            _SEGMENT_CACHE[sig] = runner
            mode = "compile"

        try:
            if _faultpoint.ACTIVE and mode == "compile":
                # compile-site fault seam: drives the eager-fallback
                # replay below, exactly like a real trace failure (the
                # runner stays cached — a later flush of the same
                # signature replays it, mirroring a transient failure)
                _faultpoint.check("engine.bulk.compile")
            c0 = _time.perf_counter() if mode == "compile" else None
            results = runner(leaves)
        except Exception:
            # a queued op turned out to be unjittable: replay the segment
            # eagerly in order so results (and real errors) match the
            # untraced path, and stop bulking the offending ops
            self._replay_eager(ops, leaves, outs, blacklist=True)
            _STATS["bulk_flushes"] += 1
            _STATS["bulk_fallbacks"] += 1
            return "eager-fallback"
        if c0 is not None:
            # compile-attribution span (ISSUE 8): the first run of a
            # fresh segment runner = trace + XLA compile + execute
            _profiler.record_compile(
                "bulk_segment", key="%d ops" % len(ops),
                dur_us=(_time.perf_counter() - c0) * 1e6)
        _STATS["bulk_flushes"] += 1
        for arr, slot, i, k in outs:
            if arr._buf is slot:  # not overwritten since queueing
                arr._buf = results[i][k]
        if _HOOKS and _profiler._LIVE and _storage._LEDGER_ON:
            # bulk-segment leaves deliver here, not at invoke (their
            # outputs were pending slots then): one ledger append per
            # delivered result, tagged with the producing op's name
            for _arr, _slot, i, k in outs:
                _LEDGER_ACT((_wref(results[i][k]), ops[i][0]))
        return mode

    @staticmethod
    def _replay_eager(ops, leaves, outs, blacklist=False):
        """Execute a popped segment op-by-op (untraced) and deliver the
        results. On an op failure, completed results are still delivered;
        arrays at/after the faulting op are re-homed to a dead segment so
        a caught exception can never let their stale op-indices resolve
        against a future batch (they raise on read instead)."""
        results = []
        try:
            for name, _statics, in_refs, call, multi in ops:
                fn = _build_traced(*call)
                ins = [leaves[r[1]] if r[0] == "l"
                       else results[r[1]][r[2]] for r in in_refs]
                try:
                    o = fn(*ins)
                except Exception:
                    if blacklist:
                        _BULK_FAILED_OPS.add(name)
                    raise
                results.append(tuple(o) if multi else (o,))
        finally:
            ledger = _HOOKS and _profiler._LIVE and _storage._LEDGER_ON
            for arr, slot, i, k in outs:
                if i < len(results) and arr._buf is slot:
                    arr._buf = results[i][k]
                    if ledger:
                        _LEDGER_ACT((_wref(results[i][k]), ops[i][0]))
                elif arr._buf is slot:
                    slot.segment = _FAILED_SEGMENT


_BULK_FAILED_OPS = set()  # mxlint: disable=MX003 (GIL-atomic adds; a racing miss re-queues one doomed op which then fails over identically)


class _DeadSegment:
    """Home of _PendingSlots whose producing flush failed: flush is a
    no-op, so NDArray._data finds the slot still pending and raises."""

    def flush(self):
        pass


_FAILED_SEGMENT = _DeadSegment()


def make_op_func(opdef, name):
    def op_func(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        res = invoke(opdef, args, kwargs)
        if out is None:
            return res
        # in-place result delivery (ref: generated wrappers' `out=` —
        # _imperative_invoke writes into the provided NDArray)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        ress = res if isinstance(res, (tuple, list)) else (res,)
        if len(outs) != len(ress):
            raise ValueError(
                "%s: out= has %d arrays but the op produces %d outputs"
                % (name, len(outs), len(ress)))
        for o, r in zip(outs, ress):
            if tuple(o.shape) != tuple(r.shape):
                raise ValueError(
                    "%s: out= array has shape %s but the result has "
                    "shape %s" % (name, tuple(o.shape), tuple(r.shape)))
            # shape/dtype peeks don't flush; deliver_result adopts a
            # still-pending bulk result instead of forcing a flush
            deliver_result(o, r)
        return out
    op_func.__name__ = name
    op_func.__doc__ = opdef.fn.__doc__
    return op_func


def populate(namespace_dict):
    """Install one wrapper per registered op name/alias into the module
    namespace (mirrors _init_op_module, ref: python/mxnet/ndarray/register.py)."""
    seen = {}
    for name in _registry.list_ops():
        opdef = _registry.get_op(name)
        if name not in namespace_dict:
            if id(opdef) not in seen:
                seen[id(opdef)] = make_op_func(opdef, opdef.name)
            fn = seen[id(opdef)]
            namespace_dict[name] = fn

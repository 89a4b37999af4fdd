"""Block / HybridBlock / SymbolBlock.

TPU-native re-design of Gluon blocks (ref: python/mxnet/gluon/block.py:178
Block, :765 HybridBlock, :966 hybridize, :859-896 _build_cache→CachedOp,
:1129 SymbolBlock). The CachedOp analog here IS ``jax.jit``: hybridize()
traces ``hybrid_forward`` once per input signature into a single XLA
computation (ref: src/imperative/cached_op.cc:96-822), with:

- cache keyed on input shapes/dtypes + train mode (SetForwardGraph's
  shape-keyed cache, cached_op.cc:307),
- whole-graph backward captured as ONE tape node via jax.vjp
  (CachedOp::Gradient, cached_op.cc:231),
- ``static_alloc`` mapping to XLA buffer donation semantics (no-op knob
  kept for API parity — XLA plans memory statically always),
- BatchNorm-style aux-state updates threaded out of the pure function and
  applied after each call (the reference mutates aux in-place inside the op).
"""
from __future__ import annotations

import functools
import re
import threading
import time as _time

import jax
import jax.numpy as jnp

from .. import autograd
from .. import profiler as _profiler
from .. import ndarray as nd
from .. import random as _random
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "make_pure_forward"]


class _BlockScope(threading.local):
    def __init__(self):
        self.counters = {}
        self.prefix = ""     # active name_scope() prefix
        self.stack = []      # per-scope counters (numbering restarts)


_SCOPE = _BlockScope()


def _gen_prefix(hint):
    counters = _SCOPE.stack[-1] if _SCOPE.stack else _SCOPE.counters
    cnt = counters.get(hint, 0)
    counters[hint] = cnt + 1
    return _SCOPE.prefix + "%s%d_" % (hint, cnt)


class _AuxCollector(threading.local):
    """Collects (param, new_data) aux updates produced during a traced
    forward so they can be returned from the pure function."""

    def __init__(self):
        self.stack = []

    def active(self):
        return bool(self.stack)

    def add(self, param, new_data):
        self.stack[-1].append((param, new_data))


_AUX = _AuxCollector()


class Block:
    """Base for all layers/models (ref: gluon/block.py:178)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        if prefix is not None:
            # explicit prefixes nest under an active name_scope, like the
            # reference's _BlockScope.create (ref: gluon/block.py:36)
            self._prefix = (_SCOPE.prefix + prefix) if prefix else prefix
        else:
            self._prefix = _gen_prefix(self._alias())
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return type(self).__name__.lower()

    # -- attribute magic: auto-register children & params -----------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    def name_scope(self):
        """Children (and explicit prefixes) created inside the scope nest
        under this block's prefix, and name numbering restarts per scope
        (ref: gluon/block.py Block.name_scope over _BlockScope)."""
        block = self

        class _NS:
            def __enter__(self_ns):
                self_ns._saved_prefix = _SCOPE.prefix
                _SCOPE.prefix = block._prefix
                _SCOPE.stack.append({})
                return block

            def __exit__(self_ns, *a):
                _SCOPE.prefix = self_ns._saved_prefix
                _SCOPE.stack.pop()
                return None
        return _NS()

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """ref: block.py collect_params — regex select supported."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret.update({n: p for n, p in self._params.items() if pat.match(n)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def _collect_params_with_prefix(self, prefix=""):
        """Structural parameter paths ("features.0.weight"), stable across
        model instances (ref: block.py _collect_params_with_prefix) — the
        serialization key space for save/load_parameters."""
        out = {}
        for name, p in self._reg_params.items():
            out[prefix + name] = p
        for cname, child in self._children.items():
            out.update(child._collect_params_with_prefix(
                prefix + cname + "."))
        return out

    # -- persistence (ref: block.py:366 save_parameters, :408 load) -------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        if deduplicate:
            seen = {}
            arg = {}
            for n, p in params.items():
                if p._data is None:
                    continue
                if id(p) in seen:
                    continue
                seen[id(p)] = n
                arg[n] = p.data()
        else:
            arg = {n: p.data() for n, p in params.items()
                   if p._data is not None}
        nd.save(filename, arg)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd.load(filename)
        canonical = self._collect_params_with_prefix()
        if loaded and canonical and not any(k in canonical for k in loaded):
            # fall back to full-name keys written by older ParameterDict.save
            params = self.collect_params()
            canonical = {}
            for n, p in params.items():
                short = n[len(self._prefix):] \
                    if n.startswith(self._prefix) else n
                canonical[short] = p
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0] if ctx else None
        for k, v in loaded.items():
            if k in canonical:
                if ctx is not None:
                    # nd.load reads onto the host; a parameter without
                    # data yet adopts the array where it is
                    v = v.as_in_context(ctx)
                if cast_dtype and dtype_source == "saved":
                    # adopt the checkpoint's dtype (ref: block.py:408
                    # load_parameters cast_dtype semantics)
                    canonical[k].cast(str(v.dtype))
                canonical[k].set_data(v)
            elif not ignore_extra:
                raise KeyError("Parameter %r in file not found in Block" % k)
        if not allow_missing:
            missing = [k for k, p in canonical.items()
                       if p._data is None and p._deferred_init is None
                       and k not in loaded]
            if missing:
                raise KeyError("Missing parameters in file: %s" % missing)

    save_params = save_parameters
    load_params = load_parameters

    # -- call path --------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        out = self(*inputs)
        lines = ["%s: %d parameters" % (self.name, sum(
            int(p.data().size) for p in self.collect_params().values()
            if p._data is not None))]
        return "\n".join(lines)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def __repr__(self):
        s = "%s(\n" % type(self).__name__
        for key, child in self._children.items():
            s += "  (%s): %s\n" % (key, repr(child).replace("\n", "\n  "))
        return s + ")"


class HybridBlock(Block):
    """Block that can be traced to one XLA computation (ref: block.py:765)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = {}
        self._static_alloc = False
        self._static_shape = False

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=None, forward_bulk_size=None,
                  backward_bulk_size=None):
        """ref: block.py:966. static_alloc/static_shape accepted for parity;
        XLA always plans memory statically."""
        self._active = active
        self._static_alloc = static_alloc
        self._static_shape = static_shape
        self._cached_graph = {}
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape)

    def infer_shape(self, *args):
        self._deferred_infer_shape(*args)

    def _deferred_infer_shape(self, *args):
        """Run an abstract (shape-only) forward to finish deferred param
        init — the analog of the reference's shape-inference pass before
        CachedOp creation (ref: block.py _deferred_infer_shape)."""
        try:
            with autograd.pause():
                jax.eval_shape(self._abstract_forward,
                               *[jax.ShapeDtypeStruct(a.shape, a.dtype)
                                 for a in args])
        except DeferredInitializationError:
            raise
        except Exception:
            # fall back: eager forward on zeros would also trigger init;
            # abstract pass can fail when params are entirely uninitialized
            raise

    def _abstract_forward(self, *datas):
        outs = self.forward(*[NDArray(d) for d in datas])
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        return tuple(o._data for o in outs)

    def cast(self, dtype):
        super().cast(dtype)
        self._cached_graph = {}

    # -- forward ----------------------------------------------------------
    def __call__(self, *args):
        if self._active:
            return self._call_cached_op(*args)
        return super().__call__(*args)

    def forward(self, x, *args):
        """Eager path: pass NDArrays + param NDArrays to hybrid_forward
        (ref: block.py:1054 HybridBlock.forward). Symbol inputs switch F
        to the symbol namespace and bind params as named variables — the
        reference's symbolic tracing path (``net(mx.sym.var('data'))``),
        which is what ONNX export and Module bind consume."""
        from ..symbol import Symbol as _Sym
        if isinstance(x, _Sym):
            from .. import symbol as _sym_mod
            params = {name: _sym_mod.var(p.name)
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(_sym_mod, x, *args, **params)
        params = {}
        for name, p in self._reg_params.items():
            try:
                params[name] = p.data()
            except DeferredInitializationError:
                self._infer_param_shapes(x, *args)
                params[name] = p.data()
        return self.hybrid_forward(nd, x, *args, **params)

    def _infer_param_shapes(self, *args):
        """Finish deferred init by running shape inference via eval_shape of
        hybrid_forward with zero-filled placeholder params."""
        hinted = self._shape_hint(*args)
        for p in self._reg_params.values():
            if p._data is None and p._deferred_init is not None:
                shape = hinted.get(p)
                if shape is None:
                    raise DeferredInitializationError(
                        "cannot infer shape for %s" % p.name)
                p._finish_deferred_init(shape)

    def _shape_hint(self, *args):
        """Subclasses (Dense/Conv/...) override to map input shapes to param
        shapes for deferred init."""
        return {}

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp analog ---------------------------------------------------
    def _call_cached_op(self, *args):
        nd_args = [a for a in args if isinstance(a, NDArray)]
        # finish deferred init first
        if any(p._data is None and p._deferred_init is not None
               for p in self._all_params_list()):
            self._deferred_init_pass(*args)
        params = self._all_params_list()
        param_datas = tuple(p.data()._data for p in params)
        training = autograd.is_training()
        from ..ndarray import register as _op_register
        sig = (tuple((a.shape, str(a.dtype)) for a in nd_args), training,
               _op_register._amp_version)
        entry = self._cached_graph.get(sig)
        # fresh signature: time trace + XLA compile + first run into the
        # compile-attribution registry (the _compile_probe convention —
        # hybridized forward compiles were invisible to the registry and
        # hence to the hlolint/roofline joins before ISSUE 18)
        c0 = _time.perf_counter() if entry is None else None
        if entry is None:
            entry = self._build_cached_graph(params, training)
            self._cached_graph[sig] = entry
        jitted, memo, aux_params = entry

        rng = _random.next_key()
        in_datas = tuple(a._data for a in nd_args)

        if autograd.is_recording():
            def run(pd, xd):
                return jitted(pd, xd, rng)
            if "lean_bwd" not in memo:
                memo["lean_bwd"] = _lean_backward(jitted) \
                    if _vjp_crowds_device(run, param_datas, in_datas) \
                    else None
            if memo["lean_bwd"] is None:
                (out_datas, aux_datas), vjp_fn = jax.vjp(
                    run, param_datas, in_datas)
            else:
                # nothing is held from forward to backward: the backward
                # program re-runs the forward itself
                out_datas, aux_datas = run(param_datas, in_datas)
                vjp_fn = functools.partial(
                    memo["lean_bwd"], param_datas, in_datas, rng)

            def vjp_flat(cts):
                if not isinstance(cts, tuple):
                    cts = (cts,)
                zero_aux = tuple(jnp.zeros(a.shape, a.dtype)
                                 for a in aux_datas)
                pd_cts, xd_cts = vjp_fn((tuple(cts), zero_aux))
                return tuple(pd_cts) + tuple(xd_cts)

            out_nds = [NDArray(o) for o in out_datas]
            inputs = [p.data() for p in params] + nd_args
            node = autograd.record_op(
                "CachedOp(%s)" % self.name, out_nds, inputs, vjp_flat)
            node.fwd_fn = None  # create_graph through cached op unsupported
        else:
            out_datas, aux_datas = jitted(param_datas, in_datas, rng)
            out_nds = [NDArray(o) for o in out_datas]

        if c0 is not None:
            _profiler.record_compile(
                "cached_graph:%s" % (self.name or type(self).__name__),
                key="%d inputs, training=%s"
                    % (len(nd_args), training),
                dur_us=(_time.perf_counter() - c0) * 1e6)

        # apply aux updates (moving stats)
        for p, new in zip(aux_params, aux_datas):
            p.data()._data = new
        return out_nds[0] if len(out_nds) == 1 else tuple(out_nds)

    def _deferred_init_pass(self, *args):
        """Finish deferred init by evaluating the forward ABSTRACTLY
        (``jax.eval_shape``): shapes flow through the layers, each leaf
        creates its parameters from the shape it sees, and nothing is
        compiled or run on the device. A hybridized block's forward is
        traceable by contract — it is about to be jitted. Running the
        pass eagerly instead made every hybridized child compile a
        program of its own just to be called once: 239 compiles for two
        ResNet-18s, 2-3 minutes of a ResNet-50's set-up on a TPU."""
        nd_pos = [i for i, a in enumerate(args) if isinstance(a, NDArray)]

        def run(*datas):
            full = list(args)
            for i, d in zip(nd_pos, datas):
                full[i] = NDArray(d)
            Block.__call__(self, *full)

        with autograd.pause():
            jax.eval_shape(run, *(args[i]._data for i in nd_pos))

    def _all_params_list(self):
        seen, out = set(), []
        for _, p in sorted(self._collect_params_with_prefix().items()):
            if id(p) not in seen:
                seen.add(id(p))
                out.append(p)
        return out

    def _build_cached_graph(self, params, training):
        """Trace the block's forward into one jitted pure function.
        Analog of CachedOp::SetForwardGraph + StaticInitExec
        (ref: src/imperative/cached_op.cc:307,584)."""
        def call(*input_nds):
            return Block.__call__(self, *input_nds)

        pure_fn, aux_params = make_pure_forward(params, call, training)
        jitted = jax.jit(pure_fn)
        # (program, per-signature memo filled on first use, aux params)
        return jitted, {}, aux_params

    def export(self, path, epoch=0):
        """Serialize architecture + params for deployment
        (ref: block.py:1004 export)."""
        params = self.collect_params()
        arg = {("arg:%s" % n): p.data() for n, p in params.items()
               if p._data is not None}
        nd.save("%s-%04d.params" % (path, epoch), arg)
        import json
        graph = {"framework": "mxnet_tpu", "block": type(self).__name__,
                 "params": sorted(params.keys())}
        with open("%s-symbol.json" % path, "w") as f:
            json.dump(graph, f, indent=2)

    # optimization barrier for API parity
    def optimize_for(self, x, backend=None, **kwargs):
        self.hybridize(True)
        return self(x)


def _device_bytes_limit(array):
    """Memory limit of the device holding ``array``; None where the
    backend reports none."""
    stats = next(iter(array.devices())).memory_stats()
    return (stats or {}).get("bytes_limit")


def _vjp_crowds_device(run, param_datas, in_datas):
    """Whether the residuals ``jax.vjp(run)`` would hold from forward to
    backward take more than half of the device's memory. A vjp across a
    jit boundary keeps every intermediate the backward reads, unfused:
    ResNet-50 at batch 128 in bf16 holds 37 GB (30 GB of it BatchNorm's
    f32 chains), so on a 16 GB chip the recorded forward — and with it
    every eager step, the fused step's warm-up included — ran out of
    memory. Decided from shapes alone, once per signature; a backend
    that reports no memory limit (the CPU) always keeps the plain vjp."""
    limit = _device_bytes_limit(param_datas[0]) if param_datas else None
    if not limit:
        return False
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (param_datas, in_datas))
    held = jax.eval_shape(lambda pd, xd: jax.vjp(run, pd, xd)[1], *avals)
    nbytes = sum(l.size * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(held))
    return nbytes > limit // 2


def _lean_backward(jitted):
    """The backward of a cached graph as ONE program that re-runs the
    forward inside itself, so XLA fuses and schedules forward and
    backward together exactly as in the one-program fused step and no
    residual ever crosses a jit boundary. Costs one more forward per
    backward; used only where the plain vjp's residuals would crowd the
    device (``_vjp_crowds_device``). Splitting the recompute from the
    backward (``jax.checkpoint`` around the forward) does not help: run
    eagerly, the recompute is its own program and hands the same
    residuals over in HBM."""
    @jax.jit  # mxlint: disable=MX005,MX022 (one per cached-graph signature, kept in that signature's memo; the forward it re-runs is the attributed cached_graph program)
    def backward(param_datas, in_datas, rng, cts):
        _, vjp_fn = jax.vjp(lambda pd, xd: jitted(pd, xd, rng),
                            param_datas, in_datas)
        return vjp_fn(cts)
    return backward


def make_pure_forward(params, call, training):
    """Build the pure-functional form of an eager forward: returns
    ``(pure_fn, aux_params)`` where ``pure_fn(param_datas, input_datas,
    rng_key) -> (out_datas, aux_datas)`` runs ``call`` with the traced
    param buffers swapped into ``params``, recording off, train mode set,
    and the PRNG stream keyed off ``rng_key``. The CachedOp purification
    seam shared by HybridBlock._build_cached_graph and the gluon fused
    train step (gluon/fused_step.py).

    Aux-state updates (BatchNorm moving stats) are threaded out of the
    pure function two ways: ``report_aux_update`` collection (eager
    stateful layers) and direct ``p.data()._data`` rebinds (a hybridized
    child applying its own cached-op aux inside this trace — previously
    those were silently dropped by the originals restore). ``aux_params``
    is repopulated on every trace, ordered like ``aux_datas``."""
    aux_params = []

    def pure_fn(param_datas, input_datas, rng_key):
        # swap traced data into the parameters, run eager forward
        originals = [p.data()._data for p in params]
        for p, d in zip(params, param_datas):
            p.data()._data = d
        _random.push_trace_key(rng_key)
        collected = []
        _AUX.stack.append(collected)
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(training)
        mutated = []
        try:
            out = call(*[NDArray(d) for d in input_datas])
        finally:
            autograd.set_training(prev_train)
            autograd.set_recording(prev_rec)
            _AUX.stack.pop()
            _random.pop_trace_key()
            for p, d, orig in zip(params, param_datas, originals):
                cur = p.data()._data
                if cur is not d and cur is not orig:
                    mutated.append((p, cur))
            for p, d in zip(params, originals):
                p.data()._data = d
        outs = out if isinstance(out, (tuple, list)) else (out,)
        aux_params.clear()
        aux_datas = []
        for p, new_data in collected + mutated:
            aux_params.append(p)
            aux_datas.append(new_data)
        return tuple(o._data for o in outs), tuple(aux_datas)

    return pure_fn, aux_params


def report_aux_update(param, new_data):
    """Called by stateful layers (BatchNorm) to publish running-stat updates.
    Under a cached-op trace the update is collected and threaded out of the
    pure function; eagerly it is applied immediately."""
    if _AUX.active():
        _AUX.add(param, new_data)
    else:
        param.data()._data = new_data


class SymbolBlock(HybridBlock):
    """Wrap a Symbol graph as a block (ref: block.py:1129). Takes a Symbol
    and input symbols; parameters come from the symbol's arguments."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol import Symbol
        self._outputs = outputs if isinstance(outputs, Symbol) else outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        input_names = {s.name for s in self._inputs}
        for argname in self._outputs.list_arguments():
            if argname not in input_names:
                p = Parameter(argname, allow_deferred_init=True)
                self._params._params[argname] = p
                self._reg_params[argname] = p
        for auxname in self._outputs.list_auxiliary_states():
            if auxname not in input_names:
                p = Parameter(auxname, grad_req="null",
                              allow_deferred_init=True)
                self._params._params[auxname] = p
                self._reg_params[auxname] = p

    @classmethod
    def imports(cls, symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load, var as sym_var
        sym = sym_load(symbol_file)
        inputs = [sym_var(n) for n in (input_names if isinstance(
            input_names, (list, tuple)) else [input_names])]
        ret = cls(sym, inputs)
        if param_file:
            loaded = nd.load(param_file)
            cleaned = {}
            for k, v in loaded.items():
                cleaned[k.split(":", 1)[-1]] = v
            for name, p in ret._params.items():
                if name in cleaned:
                    p.set_data(cleaned[name])
        return ret

    def forward(self, *args):
        """Run the wrapped graph as ONE recorded op: forward interprets the
        graph into jax (tracing into any active jit), and when autograd is
        recording the whole graph joins the tape via jax.vjp — the same
        contract as a generated op (ref: block.py:1129 SymbolBlock runs a
        CachedOp)."""
        import jax
        from .. import autograd as _ag
        from .. import random as _random
        from ..executor import _GraphProgram

        prog = getattr(self, "_prog", None)
        if prog is None:
            prog = self._prog = _GraphProgram(self._outputs)
        names = [s.name for s in self._inputs]
        nd_args = [a if isinstance(a, NDArray) else nd.array(a)
                   for a in args]
        # finish deferred param init from the graph's shape inference
        if any(p._data is None for p in self._reg_params.values()):
            shapes = {s.name: tuple(a.shape)
                      for s, a in zip(self._inputs, nd_args)}
            arg_shapes, _, aux_shapes = \
                self._outputs.infer_shape_partial(**shapes)
            arg_names = self._outputs.list_arguments()
            aux_names = self._outputs.list_auxiliary_states()
            for n, s in list(zip(arg_names, arg_shapes)) + \
                    list(zip(aux_names, aux_shapes)):
                p = self._reg_params.get(n)
                if p is not None and p._data is None and s is not None:
                    p._finish_deferred_init(tuple(s))
        param_items = list(self._reg_params.items())
        all_names = names + [n for n, _ in param_items]
        nd_inputs = nd_args + [p.data() for _, p in param_items]
        key = _random.next_key()
        training = _ag.is_training()

        datas = tuple(a._data for a in nd_inputs)
        # aux (BatchNorm moving stats) come back as EXTRA outputs so their
        # values survive jax.vjp tracing; probe the key set abstractly
        aux_keys = []
        if training:
            def probe(*d):
                return prog.run(dict(zip(all_names, d)), True, key)[1]
            try:
                aux_keys = sorted(jax.eval_shape(
                    probe, *[jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for a in datas]))
            except Exception:
                aux_keys = []

        def fwd(*datas):
            values = dict(zip(all_names, datas))
            outs, aux_up = prog.run(values, training, key)
            return tuple(outs) + tuple(
                jax.lax.stop_gradient(aux_up[k]) for k in aux_keys)

        if _ag.is_recording():
            out, vjp_fn = jax.vjp(fwd, *datas)
            all_outs = [NDArray(o) for o in out]

            def vjp_wrap(cts):
                # the tape hands a bare cotangent for single-output nodes;
                # fwd always returns a tuple
                return vjp_fn(cts if isinstance(cts, tuple) else (cts,))

            _ag.record_op("SymbolBlock", all_outs, nd_inputs, vjp_wrap)
        else:
            all_outs = [NDArray(o) for o in fwd(*datas)]
        n_real = len(all_outs) - len(aux_keys)
        outs = all_outs[:n_real]
        # deliver the moving-stat writes to the registered aux params
        # (ref: the reference's stateful BatchNorm mutating aux NDArrays)
        for name, val in zip(aux_keys, all_outs[n_real:]):
            p = self._reg_params.get(name)
            if p is not None and p._data is not None:
                p._data._data = val._data.astype(p._data._data.dtype)
        return outs[0] if len(outs) == 1 else tuple(outs)

    def hybrid_forward(self, F, *args, **kwargs):
        raise RuntimeError("SymbolBlock uses forward directly")

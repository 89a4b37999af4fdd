"""Parameter and ParameterDict.

TPU-native re-design of Gluon parameters
(ref: python/mxnet/gluon/parameter.py:47 Parameter, :507 Constant,
:705 ParameterDict). Deferred initialization (shape inferred at first
forward) is kept; multi-device replication is replaced by mesh sharding —
a Parameter holds ONE logical NDArray whose placement/sharding is governed
by the active mesh (see mxnet_tpu/parallel), not per-GPU copies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import canonical_dtype
from ..context import cpu, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import autograd
from .. import storage as _storage

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(Exception):
    """ref: python/mxnet/gluon/parameter.py:39."""


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = canonical_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._stype = stype
        self._grad_stype = grad_stype  # row_sparse -> Trainer ships rows
        self._data = None          # NDArray
        self._grad = None
        self._deferred_init = None  # (init, ctx, default_init)
        self._sharding = None      # parallel placement hint (PartitionSpec-like)
        self._trainer = None

    def _set_trainer(self, trainer):
        """ref: parameter.py _set_trainer — row_sparse params are bound to
        one trainer (they pull rows through it); dense params may move."""
        if self._stype != "default" and self._trainer is not None and \
                trainer is not None and self._trainer is not trainer:
            raise RuntimeError(
                "Failed to set the trainer for Parameter '%s' because it "
                "was already set. More than one trainers for a %s Parameter "
                "is not supported." % (self.name, self._stype))
        self._trainer = trainer

    # -- core -------------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None and req != "null":
            self._init_grad()

    def _shape_known(self):
        return self.shape is not None and all(s > 0 for s in self.shape)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """ref: parameter.py Parameter.initialize."""
        from .. import initializer as _initializer
        if self._data is not None and not force_reinit:
            return
        default_init = default_init or _initializer.Uniform()
        if not self._shape_known():
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter '%s' because it has invalid "
                "shape %s and deferred init is not allowed." % (self.name,
                                                                self.shape))
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        from .. import initializer as _initializer
        specific = init if init is not None else self.init
        initializer = specific if specific is not None else default_init
        if isinstance(initializer, str):
            initializer = _initializer.get(initializer)
        data = _np.zeros(self.shape, self.dtype)
        if specific is not None:
            # a parameter-specific initializer bypasses the name-suffix
            # dispatch (ref: initializer.py:142 — the __init__ attr path
            # calls _init_weight directly)
            if hasattr(initializer, "_init_weight"):
                initializer._init_weight(self.name, data)
            else:
                initializer(self.name, data)   # Mixed / callables
        else:
            initializer._init_weight_dispatch(self.name, data)
        ctx = ctx if ctx is not None and not isinstance(ctx, (list, tuple)) \
            else (ctx[0] if ctx else current_context())
        self._data = nd.array(data, ctx=ctx, dtype=self.dtype)
        # allocation-ledger tag upgrade: nd.array registered the buffer
        # as generic 'other'; adopting it into a Parameter makes it
        # 'param' (the specific tag wins the ledger slot)
        _storage.ledger_register(self._data, "param", site=self.name)
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self, shape):
        if self._deferred_init is None:
            raise DeferredInitializationError(
                "Parameter '%s' has not been initialized" % self.name)
        self.shape = tuple(shape)
        init, ctx, default_init = self._deferred_init
        # a hybridized block finishes deferred init inside an abstract
        # trace of its forward (HybridBlock._deferred_init_pass): the
        # parameter's buffers are concrete all the same
        with jax.ensure_compile_time_eval():
            self._finish_init(init, ctx, default_init)

    def _init_grad(self):
        self._data.attach_grad(self._grad_req)
        self._grad = self._data._grad

    # -- access -----------------------------------------------------------
    def data(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter '%s' deferred; run a forward pass or set "
                    "shape first" % self.name)
            raise RuntimeError(
                "Parameter '%s' has not been initialized. Call initialize()"
                % self.name)
        return self._data

    def list_data(self):
        return [self._data]

    def grad(self, ctx=None):
        if self._data is None or self._data._grad is None:
            raise RuntimeError("Parameter '%s' has no gradient (grad_req=%s)"
                               % (self.name, self._grad_req))
        return self._data._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [self._data.context] if self._data is not None else []

    def zero_grad(self):
        if self._data is not None and self._data._grad is not None:
            self._data._grad._data = jnp.zeros_like(self._data._grad._data)

    def set_data(self, data):
        data = data if isinstance(data, NDArray) else nd.array(data)
        known = self.shape is not None and all(
            d not in (0, None, -1) for d in self.shape)
        if known and tuple(data.shape) != tuple(self.shape):
            # ref: parameter.py Parameter._load_init shape assert — a
            # checkpoint/assignment mismatch must not pass silently
            raise ValueError(
                "Parameter %r: cannot set data of shape %s on declared "
                "shape %s" % (self.name, tuple(data.shape),
                              tuple(self.shape)))
        if self._data is None:
            self.shape = data.shape
            self._data = data
            self._deferred_init = None
            if self._grad_req != "null":
                self._init_grad()
        else:
            new = data._data.astype(self._data.dtype)
            old = self._data._data
            if isinstance(old, jax.Array) \
                    and not isinstance(old, jax.core.Tracer) \
                    and not isinstance(new, jax.core.Tracer):
                # the parameter stays where it lives: values read on the
                # host (a checkpoint) must not pull it off the chip while
                # its context goes on saying tpu(0)
                new = jax.device_put(new, old.sharding)
            self._data._data = new
        _storage.ledger_register(self._data, "param", site=self.name)

    def _adopt_fused(self, weight_data, grad_data=None):
        """Adopt one fused-train-step result into this parameter's live
        buffers: the updated weight into ``data()`` (dtype preserved)
        and, when given, the raw gradient the program computed into
        ``grad()`` — then age the grad flag, because the same program
        already consumed it (mirrors Trainer._update's bookkeeping, so
        eager and fused steps leave identical state behind)."""
        data = self.data()
        data._data = weight_data if weight_data.dtype == data.dtype \
            else weight_data.astype(data.dtype)
        # allocation-ledger choke point (ISSUE 13a): the fused step's
        # donated program produced fresh weight/grad buffers — register
        # them; the buffers they replaced retire via weakref death (CPU)
        # or is_deleted() (donation), observed by the next drain
        _storage.ledger_register(data, "param", site=self.name)
        if grad_data is not None:
            autograd.deliver_grad(data, grad_data)
            if data._grad is not None:
                _storage.ledger_register(data._grad, "grad",
                                          site=self.name)
        data._fresh_grad = False

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_context(ctx)
            if self._grad_req != "null":
                self._init_grad()

    def cast(self, dtype):
        self.dtype = canonical_dtype(dtype)
        if self._data is not None:
            self._data._data = self._data._data.astype(self.dtype)
            if self._grad_req != "null":
                self._init_grad()

    def var(self):
        from ..symbol import Symbol
        return Symbol.var(self.name, shape=self.shape)

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self.shape,
                                                      _np.dtype(self.dtype).name)


class Constant(Parameter):
    """Non-learnable parameter (ref: parameter.py:507)."""

    def __init__(self, name, value):
        value = value if isinstance(value, _np.ndarray) else \
            (value.asnumpy() if isinstance(value, NDArray) else _np.asarray(value))
        self.value = value

        from .. import initializer as _initializer

        class _CInit(_initializer.Initializer):
            def _init_weight(self, _, arr):
                arr[:] = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit())


class ParameterDict:
    """ref: python/mxnet/gluon/parameter.py:705."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __contains__(self, name):
        return name in self._params

    def __getitem__(self, name):
        return self._params[name]

    def get(self, name, **kwargs):
        """Create-or-retrieve with the dict's prefix."""
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Parameter(full, **kwargs)
            self._params[full] = param
        else:
            # update unknown shapes with now-known values
            if kwargs.get("shape") is not None:
                shape = kwargs["shape"]
                shape = (shape,) if isinstance(shape, int) else tuple(shape)
                if param.shape is None or not param._shape_known():
                    param.shape = shape
        return param

    def get_constant(self, name, value=None):
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            if value is None:
                raise KeyError("constant %r not found and no value given" % name)
            param = Constant(full, value)
            self._params[full] = param
        return param

    def _get_impl(self, full):
        if full in self._params:
            return self._params[full]
        if self._shared is not None and full in self._shared:
            self._params[full] = self._shared[full]
            return self._params[full]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("duplicate parameter name %r" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init,
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        arg = {}
        for name, p in self._params.items():
            if p._data is None:
                continue
            k = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arg[k] = p.data()
        nd.save(filename, arg)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = nd.load(filename)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise KeyError("Parameter %r missing in file %s" % (name,
                                                                    filename))
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise KeyError("File %s contains extra parameters: %s"
                               % (filename, sorted(extra)))

    def __repr__(self):
        return "ParameterDict(%s)" % ", ".join(self._params)

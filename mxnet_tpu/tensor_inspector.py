"""TensorInspector: interactive/value-check debugging for tensors
(ref: src/common/tensor_inspector.h — print_string, check for NaN/inf,
value dumping with visit-count tagging).

The reference's C++ class is constructed around a TBlob inside kernels;
here the same checks work on any NDArray / jax array / numpy array from
Python, which is where TPU debugging happens. Two device-friendly paths
(ISSUE 15 satellite):

- :meth:`TensorInspector.snapshot` inspects MANY tensors with ONE
  batched ``jax.device_get`` transfer — inspecting a whole parameter
  dict no longer round-trips the device once per tensor.
- :meth:`TensorInspector.print_in_trace` /
  :meth:`TensorInspector.check_in_trace` are ``jax.debug.print``-based
  variants usable INSIDE jitted code, where host-side numpy conversion
  is impossible — they print shape/dtype plus nonfinite/abs-max/L2 at
  run time and return the operand unchanged, so they drop into any
  traced expression."""
from __future__ import annotations

import logging

import numpy as _np

__all__ = ["TensorInspector"]


def _to_host(tensor):
    """One host copy of ``tensor`` (NDArray unwrapped first): device
    arrays go through ``jax.device_get``, host values through
    ``np.asarray``."""
    from .ndarray.ndarray import NDArray
    if isinstance(tensor, NDArray):
        tensor = tensor._data
    if isinstance(tensor, _np.ndarray):
        return tensor
    if hasattr(tensor, "sharding") or hasattr(tensor, "devices"):
        import jax
        return _np.asarray(jax.device_get(tensor))
    return _np.asarray(tensor)


class TensorInspector:
    """ref: tensor_inspector.h TensorInspector(tb, ctx)."""

    _visit_count = {}

    def __init__(self, tensor, tag=""):
        self._a = _to_host(tensor)
        self.tag = tag

    @classmethod
    def snapshot(cls, tensors, tags=None):
        """Build inspectors for many tensors with ONE batched host
        transfer (``jax.device_get`` over the whole list — the per-call
        numpy round-trip was the ISSUE 15 satellite complaint).

        ``tensors``: an iterable of NDArray/jax/numpy values, or a
        ``{name: tensor}`` dict (names become the tags). ``tags``
        optionally labels list input. Returns a list (or dict, matching
        the input shape) of :class:`TensorInspector`."""
        from .ndarray.ndarray import NDArray
        if isinstance(tensors, dict):
            names = list(tensors)
            vals = [tensors[k] for k in names]
        else:
            names = list(tags) if tags is not None else None
            vals = list(tensors)
        datas = [t._data if isinstance(t, NDArray) else t for t in vals]
        import jax
        hosts = jax.device_get(datas)
        out = [cls(_np.asarray(h),
                   tag=(names[i] if names is not None else ""))
               for i, h in enumerate(hosts)]
        if isinstance(tensors, dict):
            return dict(zip(names, out))
        return out

    def print_string(self):
        """Formatted dump with shape/dtype header (ref: print_string())."""
        return "<%s %s %s>\n%s" % (self.tag or "Tensor",
                                   "x".join(map(str, self._a.shape)),
                                   self._a.dtype,
                                   _np.array2string(self._a, threshold=64))

    def check_value(self, checker=None):
        """Return coordinates of values failing the check; default checker
        flags NaN/Inf (ref: check_value w/ CheckerType::NegativeChecker
        etc. — pass any predicate)."""
        if checker is None:
            def checker(x):
                return ~_np.isfinite(x)
        mask = checker(self._a)
        coords = [tuple(int(i) for i in idx)
                  for idx in _np.argwhere(mask)]
        if coords:
            logging.warning("TensorInspector%s: %d values failed the check "
                            "(first at %s)",
                            " [%s]" % self.tag if self.tag else "",
                            len(coords), coords[0])
        return coords

    def has_nan_or_inf(self):
        return not bool(_np.isfinite(self._a).all())

    def dump_to_file(self, tag, visit=True):
        """Save to '<tag>_<visit>.npy' with a visit counter so repeated
        passes don't overwrite (ref: dump_to_file visit-count naming)."""
        count = TensorInspector._visit_count.get(tag, 0) + 1
        if visit:
            TensorInspector._visit_count[tag] = count
        fname = "%s_%d.npy" % (tag, count)
        _np.save(fname, self._a)
        return fname

    # -- in-trace variants (usable inside jitted code) -----------------------

    @staticmethod
    def print_in_trace(x, tag=""):
        """``jax.debug.print``-based inspector usable INSIDE jitted
        code: prints ``<tag shape dtype> nonfinite/absmax/l2`` at RUN
        time (shape/dtype are trace-static and land in the format
        string; the stats are traced values) and returns ``x``
        unchanged, so it drops into any traced expression::

            y = TensorInspector.print_in_trace(y, tag="logits")
        """
        import jax
        import jax.numpy as jnp
        # the header rides as a (static) format argument, never inside
        # the format string: a tag may carry braces
        hdr = "TensorInspector[%s] <%s %s>" % (
            tag or "Tensor", "x".join(map(str, x.shape)), x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) or \
                jnp.issubdtype(x.dtype, jnp.complexfloating):
            x32 = jnp.abs(x).astype(jnp.float32)
            jax.debug.print(
                "{hdr} nonfinite={bad} absmax={amax} l2={l2}", hdr=hdr,
                bad=jnp.sum((~jnp.isfinite(x)).astype(jnp.int32)),
                amax=jnp.max(x32) if x.size else jnp.float32(0),
                l2=jnp.sqrt(jnp.sum(x32 * x32)))
        else:
            jax.debug.print("{hdr} min={mn} max={mx}", hdr=hdr,
                            mn=jnp.min(x) if x.size else 0,
                            mx=jnp.max(x) if x.size else 0)
        return x

    @staticmethod
    def check_in_trace(x, tag=""):
        """In-trace NaN/inf check: prints a warning line (via
        ``jax.debug.print``) carrying the nonfinite count — 0 on a
        clean tensor — and returns ``x`` unchanged. The in-jit sibling
        of :meth:`check_value` for code that cannot leave the trace."""
        import jax
        import jax.numpy as jnp
        bad = jnp.sum((~jnp.isfinite(x)).astype(jnp.int32)) \
            if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.int32(0)
        jax.debug.print("{hdr} nonfinite={bad}", bad=bad,
                        hdr="TensorInspector[%s] check:" % (tag or "Tensor"))
        return x

"""Runtime feature introspection (ref: python/mxnet/runtime.py,
src/libinfo.cc, include/mxnet/libinfo.h).

The reference exposes compile-time feature flags (CUDA, CUDNN, MKLDNN,
OPENCV, ...) through ``mx.runtime.Features``. Here features are detected at
import time from the live JAX/XLA runtime: which platforms (TPU/CPU) have
devices, whether pallas / distributed / native extensions are usable.
"""
from __future__ import annotations

import collections
import os

from .base import getenv as _getenv

__all__ = ["Feature", "Features", "feature_list", "use_compilation_cache"]


def use_compilation_cache():
    """Place JAX's persistent compilation cache and return its directory.

    Called by the entry points that compile for the chip (chip_smoke.py,
    bench.py, benchmark/longcontext.py, example/transformer/train_lm.py)
    before their first compile — never at package import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its cache
    there, and nothing is set in code. Otherwise the cache goes to one
    fixed directory inside the checkout, ``<repo>/.jax_cache``: the
    path is part of the cache key, so a directory that moves (a
    tempdir, a pid, a timestamp) never hits."""
    import jax
    path = _getenv("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class Feature:
    """One runtime feature flag (ref: runtime.py:28 ctypes Feature struct)."""

    def __init__(self, name, enabled):
        self._name = name
        self._enabled = bool(enabled)

    @property
    def name(self):
        return self._name

    @property
    def enabled(self):
        return self._enabled

    def __repr__(self):
        return ("✔ {}" if self._enabled else "✖ {}").format(
            self._name)


def _detect():
    import jax
    feats = collections.OrderedDict()

    platforms = set()
    try:
        for d in jax.devices():
            platforms.add(d.platform)
    except Exception:
        pass
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["CUDA"] = "gpu" in platforms or "cuda" in platforms
    # bf16 is native on TPU; the reference's F16C flag analog
    feats["BF16"] = True
    feats["F16C"] = True
    feats["INT64_TENSOR_SIZE"] = True
    feats["SIGNAL_HANDLER"] = True

    try:
        from jax.experimental import pallas  # noqa: F401
        feats["PALLAS"] = True
    except Exception:
        feats["PALLAS"] = False
    try:
        import jax.distributed  # noqa: F401
        feats["DIST_KVSTORE"] = True
    except Exception:
        feats["DIST_KVSTORE"] = False
    try:
        from . import _native
        feats["NATIVE_ENGINE"] = _native.available()
    except Exception:
        feats["NATIVE_ENGINE"] = False
    try:
        import jax.dlpack  # noqa: F401
        feats["DLPACK"] = True
    except Exception:
        feats["DLPACK"] = False
    # Data-IO features (host side, always built — pure python + native lib)
    feats["RECORDIO"] = True
    try:
        import PIL  # noqa: F401
        feats["JPEG_DECODE"] = True
    except Exception:
        feats["JPEG_DECODE"] = False
    return feats


class Features(collections.OrderedDict):
    """Map of feature name -> Feature (ref: runtime.py:72)."""

    instance = None

    def __init__(self):
        super().__init__([(n, Feature(n, e)) for n, e in _detect().items()])

    def __repr__(self):
        return "[" + ", ".join(map(repr, self.values())) + "]"

    def is_enabled(self, feature_name):
        """ref: runtime.py:86."""
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("Feature %r does not exist" % (feature_name,))
        return self[feature_name].enabled


def feature_list():
    """List of runtime Feature objects (ref: runtime.py:57)."""
    return list(Features().values())

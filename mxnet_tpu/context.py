"""Device context.

TPU-native re-design of the reference's Context (ref: python/mxnet/context.py,
include/mxnet/base.h Context struct). Devices map onto `jax.devices()`; `tpu()`
is the first-class accelerator, `cpu()` is the host, and `gpu()` is accepted as
an alias for the accelerator so that reference-style scripts written with
``ctx=mx.gpu(0)`` run unchanged on TPU.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus"]


class Context:
    """A device context. ``Context('tpu', 0)`` designates TPU chip 0.

    Unlike the reference there is no per-device thread pool to configure: XLA
    owns scheduling. The context only resolves to a concrete `jax.Device` for
    placement of buffers.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            self.device_type = device_type
            self.device_id = device_id
        if self.device_type not in self.devstr2type:
            raise ValueError("unknown device type %r" % (self.device_type,))

    # -- resolution -------------------------------------------------------
    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def jax_device(self):
        """Resolve to a concrete jax.Device (accelerator for tpu/gpu, host cpu
        otherwise). Falls back to the default backend if the requested kind is
        absent, so cpu-only CI can still run `tpu()` code — which means a
        ``tpu(0)`` context does not prove a chip: code that must be on one
        (chip_smoke.py, the bench device sections) checks the platform of
        the array's own ``devices()``. Under a
        multi-process runtime only THIS process's devices are addressable,
        so resolution is over jax.local_devices() (ref: each ps-lite worker
        owning its local GPUs, kvstore_dist.h)."""
        kind = self.device_type
        if kind in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                local = jax.local_devices(backend="cpu")
                return local[min(self.device_id, len(local) - 1)]
            except RuntimeError:
                return jax.local_devices()[0]
        devs = _accel_devices()
        if not devs:
            devs = jax.local_devices()
        return devs[self.device_id % len(devs)]

    # -- comparisons / hashing -------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """ref: Context.empty_cache (python/mxnet/context.py:161). XLA owns the
        HBM pool; this hints the runtime to free donated scratch."""
        # PJRT manages its own BFC pool; nothing to do but keep API parity.
        return None


def _accel_devices():
    for kind in ("tpu", "gpu"):
        try:
            devs = jax.local_devices(backend=kind)
            if devs:
                return devs
        except RuntimeError:
            continue
    default = jax.local_devices()
    return [d for d in default if d.platform != "cpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias for the accelerator so reference scripts run unchanged."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_accel_devices())


def num_tpus():
    return len(_accel_devices())


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value

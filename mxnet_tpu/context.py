"""Device contexts.

Parity surface: ``python/mxnet/context.py`` in the reference (Context class,
``mx.cpu()``/``mx.gpu()``, ``with ctx:`` scoping). TPU-native twist: a Context
resolves to a concrete ``jax.Device``; ``mx.tpu()`` is the accelerator
context (``mx.gpu()`` is kept as an alias so reference-era scripts run
unchanged). Device placement uses ``jax.device_put`` / default-device scoping
instead of per-op stream selection.
"""
from __future__ import annotations

import logging
import threading

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_local = threading.local()


class Context:
    """A device context (device_type, device_id)."""

    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 4: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 4}

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            dt = Context.devstr2type[device_type]
            self.device_type = Context.devtype2str[dt]
            self.device_id = device_id

    # -- jax bridge ---------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        return _resolve_device(self.device_type, self.device_id)

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
        if not hasattr(_local, "stack"):
            _local.stack = []
        _local.stack.append(self)
        return self

    def __exit__(self, *args):
        _local.stack.pop()

    # parity helper: mx.context.Context.default_ctx in reference
    @classmethod
    def _current(cls):
        stack = getattr(_local, "stack", None)
        if stack:
            return stack[-1]
        return Context("cpu", 0)


import functools


@functools.lru_cache(maxsize=None)
def _platform_devices(platform):
    """This process's ADDRESSABLE devices for a platform. Local, not
    global: in a multi-process group (jax.distributed) a Context must
    resolve to a device this worker can touch — the reference's per-worker
    local gpu(i) semantics. backend= is required: bare local_devices()
    lists only the default backend, which would make mx.cpu() resolve to a
    TPU on accelerator hosts."""
    try:
        return tuple(jax.local_devices(backend=platform))
    except RuntimeError:
        return ()


def _accel_devices():
    """This process's non-CPU jax devices (TPU chips), or [] if none."""
    for plat in ("tpu", "gpu"):
        devs = _platform_devices(plat)
        if devs:
            return list(devs)
    devs = [d for d in jax.local_devices() if d.platform != "cpu"]
    return devs


def cpu_pinned():
    """True when this process asked for the CPU platform by name
    (``JAX_PLATFORMS=cpu`` or the same through ``jax.config``)."""
    return (jax.config.jax_platforms or "").split(",")[0] == "cpu"


_warned_test_mode = False


def _resolve_device(device_type, device_id):
    if device_type == "cpu":
        cpus = _platform_devices("cpu")
        if cpus:
            # cpu ids are nominal, as in the reference: one host, any id
            return cpus[device_id % len(cpus)]
        # No CPU PJRT client exposed (accelerator-only runtime): fall back to
        # default device; host staging still happens via numpy.
        return jax.local_devices()[0]
    devs = _accel_devices()
    if not devs:
        # Test mode: a CPU-pinned process runs accelerator contexts on its
        # (virtual) CPU devices. Anywhere else a missing chip is an error.
        if not cpu_pinned():
            raise MXNetError(
                "tpu(%d) requested but JAX found no accelerator (devices: "
                "%s). Set JAX_PLATFORMS=cpu to run accelerator contexts on "
                "CPU devices." % (device_id, jax.local_devices()))
        devs = jax.local_devices()
        global _warned_test_mode
        if not _warned_test_mode:
            _warned_test_mode = True
            logging.getLogger("mxnet_tpu").warning(
                "JAX_PLATFORMS=cpu: accelerator contexts (mx.tpu(i)) run "
                "on the %d CPU device(s) of this process", len(devs))
    if not 0 <= device_id < len(devs):
        raise MXNetError(
            "tpu(%d) requested but this process has %d such device(s)"
            % (device_id, len(devs)))
    return devs[device_id]


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` for reference-script compatibility."""
    return Context("tpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_accel_devices())


def num_tpus():
    return len(_accel_devices())


def current_context():
    return Context._current()

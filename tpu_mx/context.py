"""Device context: the `mx.cpu() / mx.gpu(i) / mx.tpu(i)` layer.

TPU-native analog of the reference's Context (REF:include/mxnet/base.h,
REF:python/mxnet/context.py).  A Context is a *logical* device handle that
resolves to a concrete `jax.Device`; `tpu` is the accelerator type and `gpu`
is kept as a compatibility alias so reference-era scripts (`mx.gpu(0)`) run
unchanged on TPU.  Thread-local "current context" nesting via `with ctx:`
matches the reference semantics.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]

_DEVTYPE_ALIASES = {
    "cpu": "cpu",
    "cpu_pinned": "cpu",   # pinned host memory has no TPU distinction; alias to cpu
    "cpu_shared": "cpu",   # POSIX-shm sharing is a DataLoader detail handled host-side
    "gpu": "tpu",          # compatibility alias: mx.gpu(i) -> accelerator i
    "tpu": "tpu",
}


class Context:
    """Logical device. ``device_type`` in {cpu, tpu, gpu(alias), cpu_pinned, cpu_shared}."""

    _tls = threading.local()
    _default = None

    def __init__(self, device_type, device_id=0):
        if device_type not in _DEVTYPE_ALIASES:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution to a concrete jax.Device ---------------------------------
    @property
    def kind(self):
        return _DEVTYPE_ALIASES[self.device_type]

    def jax_device(self):
        """Resolve to a concrete jax.Device (lazily; raises if id out of range).

        Indexes the *process-local* device list: under multi-process SPMD
        (jax.distributed) `cpu(0)`/`tpu(0)` means this worker's first device —
        global devices owned by other processes are not addressable."""
        kind = self.kind
        if kind == "tpu":
            devs = _accelerator_devices()
            if not devs:
                raise RuntimeError("no accelerator devices visible to JAX")
            if self.device_id >= len(devs):
                raise RuntimeError(
                    f"device id {self.device_id} out of range ({len(devs)} accelerator(s))"
                )
            return devs[self.device_id]
        return jax.local_devices(backend="cpu")[self.device_id]

    # -- `with ctx:` ---------------------------------------------------------
    def __enter__(self):
        stack = getattr(Context._tls, "stack", None)
        if stack is None:
            stack = Context._tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()
        return False

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.kind == other.kind
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"


def _accelerator_devices():
    """This process's non-CPU jax devices; empty list when running CPU-only."""
    devs = jax.local_devices()
    accel = [d for d in devs if d.platform != "cpu"]
    return accel


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Compatibility alias for accelerator context (maps to TPU chip i)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())


def default_context():
    """Accelerator 0 if present, else cpu — the implicit creation context."""
    if Context._default is None:
        Context._default = tpu(0) if _accelerator_devices() else cpu(0)
    return Context._default


def current_context():
    stack = getattr(Context._tls, "stack", None)
    if stack:
        return stack[-1]
    return default_context()

"""Device contexts: ``mx.cpu()``, ``mx.tpu()``, ``mx.gpu()``.

Reference: ``python/mxnet/context.py:?`` — ``Context(device_type, device_id)``
with a thread-local "current context" stack used as the default placement for
every NDArray creation.

TPU-native redesign: a Context resolves to a concrete ``jax.Device``.  The
north star extends the reference's {cpu, gpu} pair with ``mx.tpu()``;
``mx.gpu()`` is kept as a compatibility alias for ``mx.tpu()`` (so reference
scripts that say ``ctx=mx.gpu(0)`` run unchanged on a TPU host); both raise
``MXNetError`` in a process that has no TPU.  Multi-device placement for
data-parallel training is a *list* of contexts, exactly like the reference's
``ctx=[mx.gpu(i) ...]``; the parallel layer (mxnet_tpu/parallel) turns such
lists into a ``jax.sharding.Mesh``.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError


class Context:
    """A device context.

    Parameters
    ----------
    device_type : str
        'cpu', 'tpu' or 'gpu' ('gpu' aliases 'tpu').
    device_id : int
        Index into this process's devices of that platform.
    """

    _local = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in ("cpu", "tpu", "gpu"):
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- jax resolution ------------------------------------------------------
    @property
    def device(self):
        """Resolve to the concrete jax.Device (lazy: jax initialises backends
        on first use)."""
        import jax

        # LOCAL devices only: in a multi-process group jax.devices() is
        # the global list, and a context on another host's device would
        # device_put to a non-addressable target (and desync the
        # process-collective bookkeeping).  Single-process, local==global.
        if self.device_type == "cpu":
            devs = jax.local_devices(backend="cpu")
        else:
            # 'tpu' and the 'gpu' compat alias name a real accelerator:
            # never hand back a CPU device under that name — a process
            # with no TPU says so here instead of running on the host
            devs = [d for d in jax.local_devices() if d.platform == "tpu"]
            if not devs:
                raise MXNetError(
                    f"context {self}: this process has no TPU device "
                    f"(jax backend is {jax.default_backend()!r}); use "
                    "mx.cpu() or the default context")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"context {self} out of range: only {len(devs)} "
                f"device(s) available"
            )
        return devs[self.device_id]

    # -- identity ------------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    # -- default-context stack ----------------------------------------------
    def __enter__(self):
        stack = getattr(Context._local, "stack", None)
        if stack is None:
            stack = Context._local.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._local.stack.pop()

    @staticmethod
    def default_ctx() -> "Context":
        stack = getattr(Context._local, "stack", None)
        if stack:
            return stack[-1]
        return _default_context()


def _default_context() -> Context:
    """The process default: the accelerator if jax has one, else cpu.

    (Reference defaults to cpu(0); we default to the TPU when present because
    that is the whole point of the port — override with ``with mx.cpu():``.)
    """
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return Context("cpu", 0)
    return Context("tpu", 0)


def cpu(device_id: int = 0) -> Context:
    """CPU context (reference: python/mxnet/context.py:? ``mx.cpu``)."""
    return Context("cpu", device_id)


def tpu(device_id: int = 0, mesh=None) -> Context:
    """TPU context — the capability the north star adds to the reference.

    ``mesh`` activates a device mesh for the process in the same call
    (``mx.tpu(mesh={'dp': 4, 'tp': 2})``): a dict builds one via
    ``parallel.make_mesh``, a ``jax.sharding.Mesh`` is used as-is.
    Parameters initialized afterwards are born replicated over it, and
    ``Trainer(..., partition_rules=...)`` / ``parallel.shard_batch``
    pick it up without further wiring."""
    if mesh is not None:
        from . import parallel  # deferred: parallel imports context

        if isinstance(mesh, dict):
            mesh = parallel.make_mesh(mesh)
        parallel.set_mesh(mesh)
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias so reference scripts run unchanged: resolves to the
    jax accelerator backend (TPU here), not an actual CUDA device."""
    return Context("gpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def num_devices(device_type: Optional[str] = None) -> int:
    """Reference analog: ``mx.context.num_gpus()`` — counts THIS
    process's devices (like CUDA device enumeration), so the canonical
    ``[mx.tpu(i) for i in range(num_tpus())]`` idiom stays valid in
    multi-process groups.  ``None`` counts the default backend's devices
    whatever their platform; 'tpu'/'gpu' count TPU devices only, matching
    what ``mx.tpu(i)`` resolves.  Use ``global_num_devices`` for mesh
    math."""
    import jax

    if device_type == "cpu":
        return len(jax.local_devices(backend="cpu"))
    devs = jax.local_devices()
    if device_type is None:
        return len(devs)
    return sum(d.platform == "tpu" for d in devs)


def global_num_devices() -> int:
    """Total devices across the process group (``jax.device_count()``)."""
    import jax

    return jax.device_count()


def num_gpus() -> int:  # compat shim for the 'gpu' alias
    return num_devices("tpu")


def num_tpus() -> int:
    return num_devices("tpu")

"""Distributed/parallel layer: device meshes, shardings, dist_tpu_sync.

Reference (SURVEY §2.3): the distributed stack is KVStore modes over
``src/kvstore/comm.h`` (local device reduce), ``kvstore_dist.h`` + ps-lite
ZMQ parameter servers (D2), NCCL (D3) and tree-allreduce; data parallelism
slices each batch across a ctx list in python (``gluon.utils.
split_and_load``) and reduces gradients through the store (§3.4).

TPU-native redesign — the heart of the north star:

  * A ``jax.sharding.Mesh`` replaces the ctx list.  Axes are named
    ``('dp', 'tp', 'pp', 'sp', 'ep')`` as needed; the default mesh is 1-D
    data-parallel over all visible devices.
  * Data parallelism = shard the global batch over ``dp`` + replicate
    parameters.  XLA GSPMD then *derives* the gradient all-reduce (psum over
    ICI) inside the compiled step — the collective the reference hand-wrote
    in comm.h/ps-lite/NCCL falls out of the partitioner, overlapped with
    backward by XLA's latency-hiding scheduler.
  * ``dist_tpu_sync`` KVStore preserves the Trainer-facing contract
    (init/push/pull/row_sparse_pull/set_optimizer) while the real work —
    the collectives — already happened inside the jit.  Its push/pull remain
    functional for eager PS-style code (the factorization-machine config).
  * Multi-host: ``initialize()`` wraps ``jax.distributed.initialize`` —
    the analog of tools/launch.py + ps-lite Postoffice bootstrap (D11/D12);
    global arrays span hosts, collectives ride ICI within a slice and DCN
    across slices.
  * Tensor/sequence parallelism (absent in the reference — D6/D8, built as
    NEW capability): ``shard_param`` places parameters over ``tp``;
    ring attention over ``sp`` lives in mxnet_tpu/parallel/ring.py.

Unit tests exercise all of this on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) — the fake-device story the
reference never had (SURVEY §4).
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from ..base import MXNetError
from ..context import Context
from ..ndarray import NDArray
from .. import telemetry

__all__ = ["initialize", "is_initialized", "make_mesh", "set_mesh",
           "current_mesh", "mesh_scope", "shard_batch", "replicate",
           "shard_param", "with_sharding", "TPUSyncKVStore", "all_sum",
           "ring_attention", "ulysses_attention", "pipeline_apply",
           "pipeline_train_1f1b", "PartitionRules", "as_rules",
           "place_params", "stacked_spec", "LLAMA_RULES", "MIXTRAL_RULES",
           "FAMILY_RULES", "last_placement", "process_sum_hostvec",
           "process_gather_hostvec"]


_STATE = threading.local()

# process-group state: True once jax.distributed.initialize succeeded in
# THIS process (single-process runs never set it)
_INITIALIZED = False


def is_initialized():
    """True when this process joined a multi-process group via
    ``initialize`` (drain consensus and other collective helpers use it
    to fall back to local behavior in single-process runs)."""
    return _INITIALIZED


def initialize(coordinator_address=None, num_processes=None, process_id=None,
               local_device_ids=None, init_retries=None, init_timeout=None,
               init_backoff=None):
    """Multi-host bootstrap (reference: tools/launch.py + ps-lite Postoffice
    handshake via DMLC_PS_ROOT_URI, SURVEY §3.4).  Call once per host before
    any jax computation; no-op for single-process runs.

    ``tools/launch.py`` sets ``MXT_COORDINATOR``/``MXT_NUM_PROCESSES``/
    ``MXT_PROCESS_ID`` — picked up here when args are omitted (the analog
    of the DMLC_* env contract).

    Elastic re-formation: a relaunched (possibly RESIZED) group re-forms
    over the same coordinator address, and transient bind/connect
    failures are routine right after a preemption (the dead group's
    socket lingers in TIME_WAIT, ranks arrive seconds apart under the
    launcher's backoff jitter).  The handshake therefore retries
    ``init_retries`` times (env ``MXT_INIT_RETRIES``, default 3) with
    exponential backoff starting at ``init_backoff`` seconds
    (``MXT_INIT_BACKOFF``, default 1.0); ``init_timeout``
    (``MXT_INIT_TIMEOUT``) bounds each barrier wait so a half-formed
    group fails fast instead of wedging until the cluster default.

    A relaunch under the launcher also surfaces WHY the previous group
    died: ``launcher.restart.<reason>`` telemetry (counter + gauge, so
    it rides every per-step JSONL record) from ``MXT_RESTART_REASON``."""
    import os
    import time as _time

    import jax

    reason = os.environ.get("MXT_RESTART_REASON")
    if reason:
        # near-zero when telemetry is off (count/gauge no-op on a flag)
        telemetry.count(f"launcher.restart.{reason}")
        telemetry.gauge("launcher.attempt",
                        int(os.environ.get("MXT_LAUNCH_ATTEMPT", "0")))
        for key, env in (("launcher.restart.crash", "MXT_RESTART_CRASHES"),
                         ("launcher.restart.preempted",
                          "MXT_RESTART_PREEMPTIONS")):
            if env in os.environ:
                telemetry.gauge(key, int(os.environ[env]))

    coordinator_address = coordinator_address or \
        os.environ.get("MXT_COORDINATOR")
    if num_processes is None and "MXT_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["MXT_NUM_PROCESSES"])
    if process_id is None and "MXT_PROCESS_ID" in os.environ:
        process_id = int(os.environ["MXT_PROCESS_ID"])
    if coordinator_address is None:
        return  # single-process
    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        try:  # loopback lane: the plain CPU backend has no cross-process
            # collectives — route them through gloo (no-op if unavailable)
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    if init_retries is None:
        init_retries = int(os.environ.get("MXT_INIT_RETRIES", "3"))
    if init_backoff is None:
        init_backoff = float(os.environ.get("MXT_INIT_BACKOFF", "1.0"))
    if init_timeout is None and "MXT_INIT_TIMEOUT" in os.environ:
        init_timeout = int(os.environ["MXT_INIT_TIMEOUT"])
    kwargs = {}
    if init_timeout is not None:
        kwargs["initialization_timeout"] = init_timeout
    global _INITIALIZED
    for attempt in range(init_retries + 1):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                local_device_ids=local_device_ids, **kwargs)
            _INITIALIZED = True
            # jax.distributed.initialize just installed XLA's preemption
            # notifier on SIGTERM; give the graceful-drain handler (if
            # the app armed one) the signal back
            import sys as _sys
            _tr = _sys.modules.get("mxnet_tpu.gluon.trainer")
            if _tr is not None:
                _tr._rearm_preemption_handler()
            return
        except Exception:
            try:  # a half-initialized client blocks the retry
                jax.distributed.shutdown()
            except Exception:
                pass
            if attempt >= init_retries:
                raise
            telemetry.count("parallel.init_retry")
            _time.sleep(init_backoff * (2 ** attempt))


def make_mesh(shape=None, axis_names=None, devices=None):
    """Create a device mesh.

    ``shape`` is a dict ``{'dp': 8}`` / ``{'dp': 4, 'tp': 2}`` or a tuple;
    defaults to 1-D data-parallel over every visible device.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = {"dp": len(devices)}
    if isinstance(shape, dict):
        axis_names = tuple(shape.keys())
        dims = tuple(shape.values())
    else:
        dims = tuple(shape)
        axis_names = tuple(axis_names or
                           ("dp", "tp", "pp", "sp", "ep")[:len(dims)])
    n = int(np.prod(dims))
    if n > len(devices):
        raise MXNetError(
            f"mesh {dims} needs {n} devices, only {len(devices)} available")
    arr = np.asarray(devices[:n]).reshape(dims)
    return jax.sharding.Mesh(arr, axis_names)


def set_mesh(mesh):
    _STATE.mesh = mesh
    return mesh


def current_mesh():
    return getattr(_STATE, "mesh", None)


class mesh_scope:
    """``with parallel.mesh_scope(mesh):`` — scoped active mesh."""

    def __init__(self, mesh):
        self._mesh = mesh
        self._prev = None

    def __enter__(self):
        self._prev = current_mesh()
        set_mesh(self._mesh)
        return self._mesh

    def __exit__(self, *exc):
        set_mesh(self._prev)


def _named_sharding(mesh, spec):
    import jax

    return jax.sharding.NamedSharding(mesh, spec)


def _pspec(*names):
    import jax

    return jax.sharding.PartitionSpec(*names)


def shard_batch(data, mesh=None, axis=0, axis_name="dp"):
    """Shard a batch over the mesh's data axis (the device_put analog of
    split_and_load's per-GPU slices — one logical array, N shards)."""
    import jax

    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call parallel.set_mesh first")
    if not isinstance(data, NDArray):
        data = NDArray(np.asarray(data))
    spec = [None] * data.ndim
    spec[axis] = axis_name
    out = NDArray.__new__(NDArray)
    out._data = jax.device_put(data._data,
                               _named_sharding(mesh, _pspec(*spec)))
    out._node, out._oidx = None, 0
    out._req_grad, out._grad, out._grad_req = False, None, "null"
    return out


def replicate(data, mesh=None):
    """Replicate an array over the whole mesh (parameter placement for DP)."""
    import jax

    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call parallel.set_mesh first")
    if isinstance(data, NDArray):
        data._data = jax.device_put(data._data,
                                    _named_sharding(mesh, _pspec()))
        return data
    return NDArray(jax.device_put(np.asarray(data),
                                  _named_sharding(mesh, _pspec())))


def shard_param(param, spec, mesh=None):
    """Tensor-parallel parameter placement (NEW capability vs reference —
    SURVEY D6): ``spec`` is a PartitionSpec-like tuple of axis names/None per
    dim, e.g. ``('tp', None)`` for row-sharded weights."""
    import jax

    mesh = mesh or current_mesh()
    if mesh is None:
        raise MXNetError("no active mesh; call parallel.set_mesh first")
    data = param.data() if hasattr(param, "data") else param
    data._data = jax.device_put(
        data._data, _named_sharding(mesh, _pspec(*spec)))
    return param


def with_sharding(raw, spec, mesh=None):
    """In-jit sharding constraint (``jax.lax.with_sharding_constraint``)
    for op authors building TP/SP models."""
    import jax

    mesh = mesh or current_mesh()
    return jax.lax.with_sharding_constraint(
        raw, _named_sharding(mesh, _pspec(*spec)))


def replicate_block_params(block, mesh=None):
    """Replicate every initialized parameter of a block over the mesh —
    the bulk placement step of DP training."""
    mesh = mesh or current_mesh()
    for p in block.collect_params().values():
        if p._data is not None:
            replicate(p._data, mesh)
            if p._data.grad is not None:
                replicate(p._data.grad, mesh)
    return block


def all_sum(arrays):
    """Eager cross-replica gradient sum (the building block of the eager
    KVStore path).

    Single-process: pass-through by construction — GSPMD backward
    delivers every gradient already reduced over the mesh in the layout
    its parameter dictates (fully replicated for DP params, partitioned
    for TP-sharded params; both are the REDUCED value, so there is
    nothing left to sum and no local property distinguishes a correct
    partitioned grad from a wrong one).

    Multi-process (``jax.process_count() > 1``): host-LOCAL gradients
    (sharding confined to this process) are flattened per dtype into ONE
    global (n, F) array over a process-axis mesh and summed with a
    single memoized jitted psum — the ps-lite allreduce hop, ridden over
    ICI/DCN collectives.  Gradients whose sharding already spans
    processes were reduced in-jit by GSPMD and pass through (summing
    them again would scale by n).  All ranks must call this collectively
    (SPMD)."""
    import jax
    import numpy as onp

    if isinstance(arrays, NDArray):
        arrays = [arrays]

    def _spans_processes(raw):
        sh = getattr(raw, "sharding", None)
        if sh is None:
            return False
        return len({d.process_index for d in sh.device_set}) > 1

    n = jax.process_count()
    if n == 1:
        return list(arrays)

    raws = [a._data if isinstance(a, NDArray) else a for a in arrays]
    out = list(arrays)
    local_idx = [i for i, r in enumerate(raws) if not _spans_processes(r)]
    if not local_idx:
        return out

    by_dtype = {}
    for i in local_idx:
        by_dtype.setdefault(onp.dtype(raws[i].dtype).name, []).append(i)
    for _dtype, idxs in sorted(by_dtype.items()):
        flat = onp.concatenate(
            [onp.asarray(raws[i]).ravel() for i in idxs])
        vec = process_sum_hostvec(flat)
        off = 0
        for i in idxs:
            size = raws[i].size
            # back onto the source grad's own placement (no default-
            # device bounce on the optimizer's hot path)
            out[i] = NDArray(jax.device_put(
                vec[off:off + size].reshape(raws[i].shape),
                raws[i].sharding))
            off += size
    return out


def process_sum_hostvec(vec):
    """Sum a host-side 1-D numpy vector across all processes (SPMD: every
    rank must call this with a same-shaped vector) and return the summed
    numpy vector.  The cross-host hop of SyncBatchNorm statistics and
    other small eager reductions; single-process it is the identity."""
    import jax
    import numpy as onp

    n = jax.process_count()
    vec = onp.asarray(vec)
    if n == 1:
        return vec
    from jax.sharding import NamedSharding, PartitionSpec

    pmesh, summed_fn = _process_psum(n)
    sharding = NamedSharding(pmesh, PartitionSpec("dp", None))
    garr = jax.make_array_from_process_local_data(
        sharding, vec.reshape(1, -1))
    out = onp.asarray(summed_fn(garr).addressable_data(0))[0]
    return out.reshape(vec.shape)


def process_gather_hostvec(vec):
    """Allgather a host-side 1-D numpy vector across all processes
    (SPMD: every rank must call this with a same-sized vector); returns
    a ``(world_size, len(vec))`` numpy matrix whose row r is rank r's
    vector.  Built as a psum of rank-slotted zeros so it reuses the
    memoized :func:`_process_psum` collective — no new jit machinery.
    Single-process returns the one-row matrix with no collective.  The
    cross-host hop of ``telemetry.fleet``'s stride exchange."""
    import jax
    import numpy as onp

    vec = onp.asarray(vec, dtype=onp.float64).ravel()
    n = jax.process_count()
    if n == 1:
        return vec.reshape(1, -1)
    r = jax.process_index()
    flat = onp.zeros(n * vec.size, dtype=vec.dtype)
    flat[r * vec.size:(r + 1) * vec.size] = vec
    return process_sum_hostvec(flat).reshape(n, vec.size)


_PROCESS_PSUM_CACHE = {}

#: reviewed signature budget (mxlint T15): the cached process-psum
#: program compiles once per (mesh, vector length) — the cache above is
#: keyed exactly on that, so steady state is its size
__compile_signatures__ = {
    "process_psum": "1 per (mesh, hostvec length)",
}


def _process_psum(n):
    """(mesh, jitted psum) over a one-device-per-process 'dp' axis,
    memoized so the hot training loop never retraces the collective."""
    import jax
    import numpy as onp

    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    devs = tuple(per_proc[i] for i in range(n))
    key = tuple(d.id for d in devs)
    hit = _PROCESS_PSUM_CACHE.get(key)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec

    pmesh = jax.sharding.Mesh(onp.asarray(devs), ("dp",))
    fn = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "dp"), mesh=pmesh,
        in_specs=PartitionSpec("dp", None),
        out_specs=PartitionSpec("dp", None)))
    _PROCESS_PSUM_CACHE[key] = (pmesh, fn)
    return pmesh, fn


class TPUSyncKVStore:
    """``dist_tpu_sync``: the KVStore facade whose allreduce rides XLA
    collectives inside the jitted step (SURVEY §2.3 D2's TPU-native
    equivalent; §5 'KVStore-shaped façade' — the north star's key trick).

    Semantics guaranteed to ``gluon.Trainer``:
      * gradients arriving at ``allreduce_grads`` are already summed over
        the global batch (GSPMD derived the psum from the sharded-batch /
        replicated-param layout), so the hook only validates layout;
      * ``init/push/pull/row_sparse_pull`` behave like a single logical
        store for eager PS-style user code.
    """

    def __init__(self):
        from .. import kvstore as kvs

        self.type = "dist_tpu_sync"
        self._local = kvs.KVStore("dist_tpu_sync_local")
        self._mesh = current_mesh()
        self._compression = None
        self._residuals = {}

    # Trainer hook.  Single-process: gradients are already globally
    # reduced by GSPMD (the in-jit psum) — nothing to move.  Multi-
    # process: each rank holds host-local gradients; sum them with one
    # collective per dtype (parallel.all_sum).  With compression
    # enabled, quantize BEFORE the cross-host hop (per-param residual),
    # exactly what the reference's compressed worker→server hop delivers.
    def allreduce_grads(self, params):
        with telemetry.span("kvstore.allreduce"):
            return self._allreduce_grads_impl(params)

    def _allreduce_grads_impl(self, params):
        import jax

        if telemetry.is_enabled():
            telemetry.count(
                "kvstore.allreduce_bytes",
                sum(telemetry.nbytes_of(g)
                    for p in params
                    for g in {id(g): g for g in p.list_grad()}.values()))
        if self._compression is not None:
            for p in params:
                # list_grad repeats the SAME handle per ctx — dedupe so
                # the residual sees each gradient exactly once
                for g in {id(g): g for g in p.list_grad()}.values():
                    q, self._residuals[p.name] = self._compression.roundtrip(
                        g, self._residuals.get(p.name))
                    g._data = q._data
        if jax.process_count() > 1:
            grads, seen = [], set()
            for p in params:
                for g in p.list_grad():
                    if id(g) not in seen:
                        seen.add(id(g))
                        grads.append(g)
            for g, s in zip(grads, all_sum(grads)):
                g._data = s._data.astype(g._data.dtype)
        return params

    @property
    def rank(self):
        import jax

        return jax.process_index()

    @property
    def num_workers(self):
        import jax

        return jax.process_count()

    @property
    def num_devices(self):
        mesh = self._mesh or current_mesh()
        if mesh is not None:
            return int(np.prod(list(mesh.shape.values())))
        import jax

        return jax.device_count()

    # -- delegate the eager store surface ------------------------------------
    def init(self, key, value):
        self._local.init(key, value)

    def push(self, key, value, priority=0):
        self._local.push(key, value, priority)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        self._local.pull(key, out, priority, ignore_sparse)

    def pushpull(self, key, value, out=None, priority=0):
        self._local.pushpull(key, value, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        self._local.row_sparse_pull(key, out, priority, row_ids)

    def broadcast(self, key, value, out=None, priority=0):
        self._local.broadcast(key, value, out, priority)

    def set_optimizer(self, optimizer):
        self._local.set_optimizer(optimizer)

    def set_updater(self, updater):
        self._local.set_updater(updater)

    def set_gradient_compression(self, compression_params):
        from ..kvstore import gradient_compression as gc

        self._compression = gc.create(compression_params)
        self._residuals = {}
        self._local.set_gradient_compression(compression_params)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        self._local.save_optimizer_states(fname, dump_optimizer)

    def load_optimizer_states(self, fname):
        self._local.load_optimizer_states(fname)


from .ring import ring_attention, ulysses_attention  # noqa: E402
from .pipeline import pipeline_apply, pipeline_train_1f1b  # noqa: E402
from .partition import (PartitionRules, as_rules, place_params,  # noqa: E402
                        stacked_spec, LLAMA_RULES, MIXTRAL_RULES,
                        FAMILY_RULES, last_placement)

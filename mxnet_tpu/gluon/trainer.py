"""gluon.Trainer — applies an optimizer to a set of Parameters.

Reference: ``python/mxnet/gluon/trainer.py:?`` — wires a ParameterDict to an
optimizer and a KVStore: ``step(batch_size)`` = allreduce grads (kvstore
push/pull) + fused optimizer update ops; ``update_on_kvstore`` moves the
update into the (possibly remote) store; saves/loads optimizer states.

TPU-native: with the single-logical-array parameter design, the
``local``/``device`` allreduce is a no-op (XLA already aggregated across the
mesh inside the backward jit).  ``dist_tpu_sync`` installs a psum-based
fused (allreduce + update) path (mxnet_tpu/parallel) — the north star's key
trick: the Trainer API is unchanged while the whole step compiles into one
XLA program with collectives on ICI.
"""
from __future__ import annotations

import signal
import threading
import time

from jax.profiler import TraceAnnotation

from ..base import MXNetError
from .parameter import Parameter, ParameterDict
from .. import optimizer as opt
from .. import sanitizer as _san
from .. import telemetry
from ..telemetry import costs as _costs
from ..telemetry import memwatch as _mw
from ..telemetry import numerics as _numerics
from ..telemetry import retrace as _retrace
from ..telemetry import tracing

__all__ = ["Trainer", "PREEMPTED_EXIT_CODE", "install_preemption_handler",
           "drain_requested", "drain_consensus", "request_drain",
           "reset_drain"]


# -- preemption drain ---------------------------------------------------------
# Cloud schedulers deliver SIGTERM, wait a grace period, then SIGKILL.
# The reference loses the in-flight interval of work (do_checkpoint is
# epoch-grained and SIGTERM default-kills python).  Here SIGTERM only
# sets a flag; the training loop polls ``drain_requested()`` after each
# completed step, cuts a final checkpoint, and exits with
# ``PREEMPTED_EXIT_CODE`` so tools/launch.py can tell a graceful drain
# from a crash (see checkpoint.drain_checkpoint_and_exit and
# docs/fault_tolerance.md).

#: BSD EX_TEMPFAIL: "transient failure, retry later" — the drain path's
#: exit status.  tools/launch.py mirrors the value (it stays stdlib-only)
#: and maps it to a backoff relaunch that does NOT consume the crash
#: restart budget.
PREEMPTED_EXIT_CODE = 75

#: reviewed signature budget (mxlint T15): the fused update compiles one
#: program per (optimizer type, rescale_grad, mixed-precision flags,
#: weight avals, state widths, mesh, numerics mode); a varying
#: ``step(batch_size)`` varies rescale_grad and retraces — hold the batch
#: size steady or rescale outside the step
__compile_signatures__ = {
    "trainer_fused": "1 per (optimizer, rescale_grad, mp flags, weight "
                     "avals, state widths, mesh, numerics)",
}

_DRAIN = threading.Event()

# signals the user armed — parallel.initialize re-installs the handler
# for these after the distributed handshake (jax.distributed.initialize
# registers XLA's own preemption notifier on SIGTERM, silently replacing
# any handler armed earlier)
_ARMED_SIGNUMS = []


def install_preemption_handler(signums=(signal.SIGTERM,)):
    """Arm the graceful-drain contract: the given signals set the drain
    flag (and count ``trainer.drain_signal``) instead of killing the
    process.  Must run on the MAIN thread (a ``signal.signal``
    requirement) before training starts.  Returns the drain event.

    Safe to call before OR after ``parallel.initialize`` — initialize
    re-arms it, because ``jax.distributed.initialize`` installs XLA's
    preemption notifier over the process SIGTERM handler."""

    def _on_signal(_signum, _frame):
        _DRAIN.set()
        telemetry.count("trainer.drain_signal")

    for signum in signums:
        signal.signal(signum, _on_signal)
    _ARMED_SIGNUMS[:] = list(signums)
    return _DRAIN


def _rearm_preemption_handler():
    """Called by ``parallel.initialize`` after the jax.distributed
    handshake to win back the signal(s) from XLA's notifier."""
    if _ARMED_SIGNUMS:
        install_preemption_handler(tuple(_ARMED_SIGNUMS))


def drain_requested():
    """True once a drain signal arrived — poll after each completed step."""
    return _DRAIN.is_set()


def drain_consensus():
    """True iff ANY rank has ``drain_requested()`` — collectively agreed.

    A real preemption TERMs one VM, not the whole group; the signalled
    rank alone leaving the step loop would strand its peers inside the
    next gradient allreduce.  Polling THIS after each step instead makes
    every rank learn of the drain at the same step boundary (the flag
    rides a tiny host-vector psum, itself a synchronization point), so
    the group exits together and the drain checkpoint is consistent.
    Single-process it degenerates to ``drain_requested()`` at no cost."""
    local = _DRAIN.is_set()
    from .. import parallel
    if not parallel.is_initialized():
        return local
    import numpy as np

    return parallel.process_sum_hostvec(
        np.array([1.0 if local else 0.0]))[0] > 0


def request_drain():
    """Programmatic drain (tests, in-process schedulers)."""
    _DRAIN.set()


def reset_drain():
    """Clear the drain flag (a new run in the same process)."""
    _DRAIN.clear()


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None, partition_rules=None, mesh=None,
                 offload=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError(
                "params must be a ParameterDict, dict, or list of Parameters")
        self._params = []
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise MXNetError(f"element {i} is not a Parameter")
            self._param2idx[param.name] = i
            self._params.append(param)
        # GSPMD entry point: partition_rules (a parallel.PartitionRules,
        # a family name like "llama"/"mixtral", or an ordered
        # (regex, spec) table) places every initialized parameter — and
        # its grad — with NamedSharding over the mesh at construction.
        # Optimizer state and multi-precision masters inherit the layout
        # when _init_states builds them (both follow weight._data.
        # sharding), so the whole optimizer trains in the TP/EP layout
        # with no further user code.  mesh= may be a Mesh or a
        # {'dp': 4, 'tp': 2} dict; it becomes the process mesh when none
        # is active so shard_batch and late param inits see it.
        self._partition_rules = None
        self._mesh = None
        self._placement = None
        if partition_rules is not None or mesh is not None:
            from .. import parallel

            if isinstance(mesh, dict):
                mesh = parallel.make_mesh(mesh)
            mesh = mesh if mesh is not None else parallel.current_mesh()
            if mesh is None:
                raise MXNetError(
                    "Trainer(partition_rules=...) needs a device mesh: "
                    "pass mesh= or activate one (mx.tpu(mesh=...) / "
                    "parallel.set_mesh)")
            if parallel.current_mesh() is None:
                parallel.set_mesh(mesh)
            self._mesh = mesh
            rules = parallel.as_rules(partition_rules) \
                if partition_rules is not None else \
                parallel.PartitionRules(((r".*", ()),))  # mesh-only: DP
            self._partition_rules = rules
            self._placement = parallel.place_params(
                self._params, rules, mesh=mesh)
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_params = {
            "kvstore": kvstore, "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._fused_cache = {}  # sig -> jitted multi-tensor update
        self._fused_compiled = False   # did the last update build one
        self._steps = 0   # step() calls: the lane log's ``seq``
        # offload="host": optimizer state + f32 masters live in host
        # memory between steps (mxnet_tpu.memory.offload); the update
        # donates transient device copies, so the donation contract and
        # sanitizer are unchanged.  Frees n_state x params (+ masters)
        # of HBM for configs near the budget wall.
        if offload not in (None, "host"):
            raise MXNetError(
                f'offload must be None or "host", got {offload!r}')
        self._offload = offload
        self._offload_prefetched = {}

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            if contexts is not None and contexts != ctx:
                raise MXNetError(
                    f"all Parameters must share contexts; {param.name} has "
                    f"{ctx} vs {contexts}")
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            if optimizer_params:
                raise MXNetError(
                    "optimizer_params must be None when optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._states = [None] * len(self._params)
        self._states_initialized = [False] * len(self._params)

    def _init_states(self, i):
        if not self._states_initialized[i]:
            param = self._params[i]
            self._states[i] = \
                self._optimizer.create_state_multi_precision(
                    i, param.data())
            self._states_initialized[i] = True
            if self._offload == "host":
                from ..memory import offload as _mem_offload

                for arr in self._offloaded_ndarrays(i):
                    _mem_offload.stash(arr)

    def _offloaded_ndarrays(self, i):
        """The host-resident NDArrays of param i's optimizer state: the
        f32 master (multi-precision) plus every flattened state
        tensor."""
        import numpy as np

        st = self._states[i]
        if st is None:
            return []
        param = self._params[i]
        use_mp = self._optimizer.multi_precision and \
            np.dtype(param.dtype).name in ("float16", "bfloat16")
        arrs = []
        if use_mp and isinstance(st, tuple) and len(st) == 2:
            master, sub = st
            arrs.append(master)
            arrs.extend(opt._flatten_state(sub))
        else:
            arrs.extend(opt._flatten_state(st))
        return arrs

    def _prefetch_offloaded(self):
        """Kick off async H2D of every host-stashed state buffer at the
        TOP of the step, so the copies overlap the gradient allreduce
        instead of serializing before the fused update."""
        if self._offload != "host":
            return
        from ..memory import offload as _mem_offload

        cache = {}
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or not self._states_initialized[i]:
                continue
            for arr in self._offloaded_ndarrays(i):
                cache[id(arr)] = _mem_offload.fetch(arr)
        self._offload_prefetched = cache

    def _fetch_offloaded(self, arr):
        """The prefetched device copy of a host-stashed NDArray's
        buffer, or a fresh H2D fetch (first step: states were created
        after the prefetch point)."""
        raw = self._offload_prefetched.pop(id(arr), None)
        if raw is not None:
            return raw
        from ..memory import offload as _mem_offload

        return _mem_offload.fetch(arr)

    def _stash_offloaded(self, live):
        """Move the freshly committed state buffers back to host (D2H,
        async) after the update; the replaced host copies are released
        from the accounting."""
        from ..memory import offload as _mem_offload

        for i in live:
            for arr in self._offloaded_ndarrays(i):
                _mem_offload.release(arr)
                _mem_offload.stash(arr)

    def _init_kvstore(self):
        config = self._kvstore_params
        kvstore = config["kvstore"]
        update_on_kvstore = config["update_on_kvstore"]
        if kvstore is None:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            from .. import kvstore as kvs

            kv = kvs.create(kvstore) if isinstance(kvstore, str) else kvstore
            self._kvstore = kv
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            if getattr(kv, "type", "") == "dist_async":
                # async PS applies updates server-side on arrival; a
                # client-side update would race stale pulls (reference
                # kvstore_dist.h has the same update_on_kvstore=True
                # requirement for dist_async)
                if update_on_kvstore is False:
                    raise MXNetError(
                        "dist_async requires update_on_kvstore=True")
                update_on_kvstore = True
            elif update_on_kvstore is None:
                # single logical array: updating locally is strictly better
                # (fused jit update); dist PS-style configs opt in explicitly
                update_on_kvstore = False
            self._update_on_kvstore = update_on_kvstore
            if update_on_kvstore:
                for i, param in enumerate(self._params):
                    if param.grad_req != "null":
                        self._kvstore.init(i, param.data())
                self._kvstore.set_optimizer(self._optimizer)
                self._shipped_hparams = self._hparams_sig()
        self._kv_initialized = True

    def _hparams_sig(self):
        lr = None if self._optimizer.lr_scheduler is not None \
            else self._optimizer.lr
        return (lr, self._optimizer.rescale_grad, self._optimizer.wd)

    def _sync_kvstore_hparams(self):
        """The server holds a pickled optimizer COPY; re-sync lr /
        rescale_grad / wd whenever they change locally (set_learning_rate,
        a different batch_size) so the server never trains on stale
        hyperparameters.  lr under an LRScheduler progresses server-side
        (the server's num_update advances as it applies updates)."""
        ship = getattr(self._kvstore, "set_optimizer_hparams", None)
        if ship is None:
            return
        sig = self._hparams_sig()
        if sig != getattr(self, "_shipped_hparams", None):
            ship(lr=sig[0], rescale_grad=sig[1], wd=sig[2])
            self._shipped_hparams = sig

    # -- public properties ---------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def placement(self):
        """The partition-rules :class:`parallel.partition.Coverage`
        report from construction (None without partition_rules/mesh)."""
        return self._placement

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def attach_data_prefetcher(self, prefetcher):
        """Associate a ``data.DevicePrefetcher`` (or a
        ``data.StreamingLoader`` wrapping one) with this trainer: every
        ``step()`` samples its buffered-batch depth right after the
        update dispatch — the moment the NEXT batch's transfer should
        already be in flight.  A healthy overlapped pipeline holds the
        ``data.prefetch_depth`` gauge near its configured depth; a
        starving one sits at 0 (docs/data.md)."""
        self._data_prefetcher = prefetcher

    def _poke_data_prefetcher(self):
        p = getattr(self, "_data_prefetcher", None)
        if p is None:
            return
        # StreamingLoader wraps the prefetcher; accept either
        q = getattr(getattr(p, "_prefetcher", p), "_q", None)
        if q is not None:
            telemetry.gauge("data.prefetch_depth", q.qsize())

    # -- the step ------------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """Allreduce gradients and apply one optimizer update, scaling
        gradients by 1/batch_size (reference: ``Trainer.step``)."""
        t0 = time.perf_counter()
        self._steps += 1
        seq = self._steps
        with TraceAnnotation("mxt.trainer.step", seq=seq), \
                telemetry.span("trainer.step"):
            # rescale is set BEFORE kvstore init: update_on_kvstore ships a
            # pickled optimizer copy to the (possibly remote) server, so it
            # must already carry the right rescale_grad at that point
            self._optimizer.rescale_grad = self._scale / batch_size
            if not self._kv_initialized:
                self._init_kvstore()
            if self._update_on_kvstore:
                self._sync_kvstore_hparams()
            self._prefetch_offloaded()
            t_allreduce0 = time.perf_counter()
            with TraceAnnotation("mxt.trainer.allreduce", seq=seq):
                self._allreduce_grads()
            t_update0 = time.perf_counter()
            self._fused_compiled = False
            with TraceAnnotation("mxt.trainer.update", seq=seq):
                self._update(ignore_stale_grad)
            t_update1 = time.perf_counter()
            self._offload_prefetched = {}
            self._poke_data_prefetcher()
        # the per-step path's record in the always-on lane log: the
        # all-reduce ends where the update starts, the update's
        # dispatch is the step's (``k`` = 1 optimizer step)
        tracing.lane_record(
            "train.dispatch", path="trainer.step", seq=seq, k=1,
            compiled=self._fused_compiled, t0=t0, t_args=t_allreduce0,
            t_allreduce0=t_allreduce0, t_allreduce1=t_update0,
            t_update0=t_update0, t_update1=t_update1, t_disp1=t_update1,
            t_end=time.perf_counter())

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "allreduce_grads() is not supported when update_on_kvstore "
                "is True (the store owns the update)")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        t0 = time.perf_counter() if telemetry.is_enabled() else None
        try:
            self._allreduce_grads_inner()
        finally:
            if t0 is not None:
                # wall time the step spent in gradient aggregation —
                # the fleet exchange packs this so straggler detection
                # can split compute skew from allreduce-wait skew
                telemetry.count("trainer.allreduce_wait_ms",
                                (time.perf_counter() - t0) * 1e3)

    def _allreduce_grads_inner(self):
        with telemetry.span("trainer.allreduce"):
            reducer = getattr(self._kvstore, "allreduce_grads", None)
            if telemetry.is_enabled() and reducer is None:
                # gradient payload the push/pull path aggregates; stores
                # with their own reducer (dist_tpu_sync) count the same
                # payload as kvstore.allreduce_bytes — never both
                telemetry.count("trainer.allreduce_bytes", sum(
                    telemetry.nbytes_of(p._data.grad)
                    for p in self._params
                    if p.grad_req != "null" and p._data is not None and
                    p._data.grad is not None))
            if reducer is not None:
                # dist_tpu_sync: psum over the mesh (mxnet_tpu/parallel)
                reducer([p for p in self._params if p.grad_req != "null"])
                return
            if self._update_on_kvstore:
                return  # push happens in _update
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.init(i, param.grad())
                    self._kvstore.push(i, param.grad())
                    self._kvstore.pull(i, param.grad())

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError(
                "update() is not supported when update_on_kvstore is True")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        with telemetry.span("trainer.update"):
            self._update_impl(ignore_stale_grad)

    def _update_impl(self, ignore_stale_grad=False):
        if not self._update_on_kvstore and self._try_fused_update():
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if param._data is None:
                if param._deferred_init is not None:
                    continue  # untouched deferred param: nothing to update
                raise MXNetError(
                    f"parameter {param.name} was not initialized")
            if self._update_on_kvstore:
                self._kvstore.push(i, param.grad())
                self._kvstore.pull(i, param.data())
                continue
            self._init_states(i)
            if self._offload == "host":
                # eager fallback: rebind the host-resident optimizer
                # tensors to device copies for the in-place update, then
                # send the results back to host
                from ..memory import offload as _mem_offload
                offed = self._offloaded_ndarrays(i)
                for arr in offed:
                    raw = self._fetch_offloaded(arr)
                    _mem_offload.release(arr)
                    arr._data = raw
                self._optimizer.update_multi_precision(
                    i, param.data(), param.grad(), self._states[i])
                for arr in offed:
                    _mem_offload.stash(arr)
            else:
                self._optimizer.update_multi_precision(
                    i, param.data(), param.grad(), self._states[i])

    # -- fused multi-tensor update -------------------------------------------
    # The reference fuses optimizer updates across params into single
    # kernels (multi_sgd_update / preloaded_multi_sgd_*, SURVEY §2.2
    # optimizer-ops row) because per-param launches dominate for nets with
    # many small tensors.  Here ALL per-param ``_step`` rules trace into
    # ONE jitted program: a single dispatch per training step, and XLA
    # fuses across tensors.  lr/wd/t enter as traced scalars so LR
    # schedules don't retrace.
    def _try_fused_update(self):
        from .. import engine
        from ..ndarray import sparse as sp

        if engine.is_naive():
            return False  # NaiveEngine: per-param eager updates
        optzr = self._optimizer
        if type(optzr)._step is opt.Optimizer._step:
            return False  # optimizer has no pure step rule
        live = []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if param._data is None:
                if param._deferred_init is not None:
                    continue
                raise MXNetError(
                    f"parameter {param.name} was not initialized")
            if isinstance(param.grad(), sp.BaseSparseNDArray):
                return False  # sparse grads use the lazy eager path
            live.append(i)
        if not live:
            return True
        import jax
        import numpy as np

        for i in live:
            self._init_states(i)
            optzr._update_count(i)
        weights, grads, states, masters = [], [], [], []
        lrs, wds, ts = [], [], []
        mp_flags = []
        for i in live:
            param = self._params[i]
            state = self._states[i]
            use_mp = optzr.multi_precision and \
                np.dtype(param.dtype).name in ("float16", "bfloat16")
            if use_mp:
                master, sub_state = state
                masters.append(master)
                states.append(opt._flatten_state(sub_state))
            else:
                masters.append(None)
                states.append(opt._flatten_state(state))
            mp_flags.append(use_mp)
            weights.append(param.data())
            grads.append(param.grad())
            lrs.append(optzr._get_lr(i))
            wds.append(optzr._get_wd(i))
            ts.append(optzr._index_update_count[i])

        from .. import parallel

        mesh = self._mesh if self._mesh is not None \
            else parallel.current_mesh()
        # the mesh is part of the compile signature: the same shapes
        # lower to different programs (collectives, per-device tiles)
        # under different meshes, and the cost registry keys one
        # artifact per (signature, mesh)
        mesh_sig = None if mesh is None else tuple(mesh.shape.items())
        sig = (type(optzr).__name__, float(optzr.rescale_grad),
               tuple(mp_flags),
               tuple((w.shape, str(w.dtype)) for w in weights),
               tuple(len(s) for s in states), mesh_sig,
               _numerics.signature())
        fn = self._fused_cache.get(sig)
        compiling = self._fused_compiled = fn is None
        if compiling:
            telemetry.count("trainer.fused_cache_miss")
            if _retrace._enabled:
                # registered compile site: a post-warmup second fused
                # signature (new weight schema, optimizer closure attr,
                # mesh or numerics mode) is a retrace
                _retrace.observe(
                    "trainer_fused", id(self),
                    {"optimizer": sig[0], "rescale_grad": sig[1],
                     "mp_flags": sig[2], "weights": sig[3],
                     "state_widths": sig[4], "mesh": sig[5],
                     "numerics": sig[6]},
                    site="mxnet_tpu.gluon.trainer:"
                         "Trainer._try_fused_update")
            flags = tuple(mp_flags)
            # baked at trace time; the signature above keys on it, so
            # stats-on and stats-off each keep one fused program
            numerics_on = _numerics.trace_enabled()

            def fused(w_raws, m_raws, g_raws, s_raws, lr_v, wd_v, t_v):
                new_w, new_m, new_s = opt._fused_param_updates(
                    optzr, flags, w_raws, m_raws, g_raws, s_raws,
                    lr_v, wd_v, t_v)
                # grad + update-delta stats fold into the SAME donated
                # compile — reading the donated w_raws here is fine, the
                # trace is functional (donation is a buffer-reuse hint)
                nstats = tuple(
                    (_numerics.stats_of(g), _numerics.stats_of(nw - ow))
                    for g, nw, ow in zip(g_raws, new_w, w_raws)) \
                    if numerics_on else ()
                return new_w, new_m, new_s, nstats

            # donate weights, masters and states; grads are read-only
            fn = jax.jit(fused, donate_argnums=(0, 1, 3))
            self._fused_cache[sig] = fn

        import jax.numpy as jnp

        w_raws = tuple(w._data for w in weights)
        if self._offload == "host":
            # state/masters are host-resident: feed (prefetched) device
            # copies to the donating jit — the donated buffers are the
            # transients, never the host originals
            m_raws = tuple(self._fetch_offloaded(m)
                           for m in masters if m is not None)
            s_raws = tuple(tuple(self._fetch_offloaded(s) for s in ss)
                           for ss in states)
        else:
            m_raws = tuple(m._data for m in masters if m is not None)
            s_raws = tuple(tuple(s._data for s in ss) for ss in states)
        g_raws = tuple(g._data for g in grads)
        lr_v = jnp.asarray(lrs, jnp.float32)
        wd_v = jnp.asarray(wds, jnp.float32)
        t_v = jnp.asarray(ts, jnp.int32)
        if _costs._enabled:
            # registered BEFORE the donating dispatch (lower() reads avals
            # only); keyed by the fused-jit cache signature so replays hit
            _costs.note("trainer_fused", (id(self), sig), fn,
                        (w_raws, m_raws, g_raws, s_raws, lr_v, wd_v, t_v),
                        site="mxnet_tpu.gluon.trainer:"
                             "Trainer._try_fused_update")
        # first dispatch per signature pays trace+compile synchronously;
        # replays are a single async dispatch
        try:
            with telemetry.span("trainer.fused_compile" if compiling
                                else "trainer.fused_update"):
                new_w, new_m, new_s, nstats = fn(
                    w_raws, m_raws, g_raws, s_raws, lr_v, wd_v, t_v)
        except Exception as exc:
            if _mw._enabled:
                _mw.annotate_oom(exc, context="Trainer fused update")
            raise
        if _mw._enabled:
            # the device freed the donated buffers at dispatch
            _mw.donated(
                w_raws + m_raws + tuple(r for ss in s_raws for r in ss))
        if _san._enabled:
            # the dispatch donated the old weight/master/state buffers;
            # poison them so any stale view (a detach() taken before the
            # step) fails with this site.  _commit_param_updates rebinds
            # the live holders to the result buffers, clearing them.
            _san.donate(
                w_raws + m_raws + tuple(r for ss in s_raws for r in ss),
                "Trainer._try_fused_update (gluon/trainer.py, fused "
                "multi-tensor update, donate_argnums=(0, 1, 3))")
        opt._commit_param_updates(self, live, mp_flags, masters,
                                  new_w, new_m, new_s)
        if nstats:
            # device scalars queued for the stride harvest — no host
            # transfer on the update path
            names, stats = [], []
            for i, (gs, us) in zip(live, nstats):
                pname = self._params[i].name
                names += ["grad." + pname, "update." + pname]
                stats += [gs, us]
            _numerics.record_compiled(names, stats)
        if self._offload == "host":
            # holders now point at the fresh device results; move the
            # optimizer side back to host for the inter-step window
            self._stash_offloaded(live)
        return True

    # -- state persistence (reference: Trainer.save_states/load_states) ------
    def save_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
            return
        import pickle

        for i, param in enumerate(self._params):
            if param.grad_req != "null":
                self._init_states(i)
        payload = {
            "states": {i: opt._states_to_numpy(s)
                       for i, s in enumerate(self._states)},
            "num_update": self._optimizer.num_update,
            "index_update_count": dict(
                self._optimizer._index_update_count),
        }
        with open(fname, "wb") as f:
            pickle.dump(payload, f)

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        import pickle

        with open(fname, "rb") as f:
            payload = pickle.load(f)
        self._states = [opt._states_from_numpy(s)
                        for _, s in sorted(payload["states"].items())]
        self._states_initialized = [True] * len(self._states)
        self._optimizer.num_update = payload["num_update"]
        self._optimizer._index_update_count.update(
            payload["index_update_count"])

"""Device-side multi-step training: K optimizer steps in ONE program.

Reference analog: the reference's executor dispatches one training step
per ``Forward``/``Backward``/``update`` round trip
(src/executor/graph_executor.cc:?, python/mxnet/gluon/trainer.py:?) —
cheap there because the host sits on the same PCIe bus as its
accelerator.  On TPU per-step launch latency is the scarce resource:
what separates a per-step loop from a single dispatched chain is host
round trips between steps, not chip time.

The TPU-idiomatic fix (Keras calls it ``steps_per_execution``; jax
training loops use ``lax.scan`` over the step body) is to compile K
whole optimizer steps — forward, backward, parameter update — into one
XLA program and dispatch it once.  ``FusedTrainStep`` does that for a
stock gluon ``net`` + ``Trainer``: the step body reuses the same pure
tracing machinery as CachedOp (param-handle substitution,
``_CachedGraph._pure``) and the same per-optimizer functional update
rules (``Optimizer._step``) that the fused multi-tensor update already
traces, then ``lax.scan``s the body K times with parameters, optimizer
state, mutable aux (BN running stats), update counts and the PRNG key
threaded through the carry.

Semantics vs K eager steps:
- gradients are d(sum of every loss element)/dw — exactly the ones the
  tape seeds on ``loss.backward()`` — rescaled by the optimizer's
  ``rescale_grad`` (set from ``scale / batch_size`` like
  ``Trainer.step``);
- hyperparameters (lr, wd) are read once per execution, so an LR
  schedule advances at execution granularity (the Keras
  ``steps_per_execution`` contract); the per-param update count ``t``
  DOES advance every inner step (bias correction in Adam/LAMB stays
  exact);
- dropout draws a fresh folded key each inner step;
- distributed modes that hand the update to a kvstore
  (``update_on_kvstore``) or use sparse gradients are not fusable —
  construction raises and the caller falls back to per-step dispatch.

Inputs may be per-execution constants (a synthetic batch reused K
times) or stacked ``(K, ...)`` leaves scanned one slice per inner step.

Side values of a step.  A net (or the ``forward_loss`` around it) hands
small per-step values out of the program with :func:`report`: under the
fused trace they are stacked over the K steps like the losses and stay on
the device (``FusedTrainStep.reported``) until the caller takes them with
``fetch_reported()``, one dispatch behind like the losses; the per-step
path collects the same values eagerly under :func:`reported`.  Static
facts of the trace (a str or a number) are kept once.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from .. import autograd as ag
from .. import optimizer as opt
from .. import sanitizer as _san
from .. import telemetry
from ..telemetry import costs as _costs
from ..telemetry import memwatch as _mw
from ..telemetry import numerics as _numerics
from ..telemetry import retrace as _retrace
from ..telemetry import tracing
from ..base import MXNetError
from ..ndarray import NDArray
from .block import _trace_guard

__all__ = ["FusedTrainStep", "report", "reported"]

import threading as _threading

_TLS = _threading.local()


def report(**values):
    """Hand named values of this training step to whoever collects them:
    ``FusedTrainStep`` while it traces (NDArrays, stacked over its K steps
    and fetched with ``fetch_reported``; a str or a number is a fact of
    the trace, kept once) or an enclosing :func:`reported` (the per-step
    path, eagerly).  With neither, nothing happens."""
    sink = getattr(_TLS, "sink", None)
    if sink is not None:
        sink.update(values)


class reported:
    """Collect what the forward :func:`report` s, eagerly::

        with step_fusion.reported() as side:
            with autograd.record():
                loss = forward_loss(net, *batch)
        side["loss_main"]          # an NDArray

    Nests: the innermost collector takes the values."""

    def __enter__(self):
        self._prev = getattr(_TLS, "sink", None)
        _TLS.sink = {}
        return _TLS.sink

    def __exit__(self, *exc):
        _TLS.sink = self._prev
        return False

#: reviewed signature budget (mxlint T15): one fused program per
#: (batch avals, param set, optimizer config, k) — a FusedTrainStep is
#: built once per training setup and replayed, so steady state is 1
__compile_signatures__ = {
    "step_fusion": "1 per (batch avals, param set, optimizer, k_steps)",
}


def _mem_policy_tier():
    """The last-selected remat tier, or None — probed via sys.modules so
    the memory package stays unimported unless the user opted in."""
    import sys

    mem = sys.modules.get("mxnet_tpu.memory")
    if mem is None:
        return None
    try:
        pol = mem.policy.last_policy()
        return pol["tier"] if pol is not None else None
    except Exception:
        return None


class FusedTrainStep:
    """Compile ``steps_per_execution`` trainer steps into one dispatch.

    Parameters
    ----------
    net : Block
        The model.  Must be initialized with shapes resolved (run one
        forward first); hybridized or not — the trace inlines either.
    trainer : gluon.Trainer
        Owns the parameters and optimizer.  The fused program applies
        the SAME functional update rules (``Optimizer._step``) the
        trainer's fused multi-tensor path uses.
    forward_loss : callable
        ``forward_loss(net, *batch) -> loss NDArray (any pytree)``.
        Runs the model and returns the training loss; traced once.
    steps_per_execution : int
        K — how many optimizer steps one dispatch performs.
    batch_size : int
        Gradient rescale denominator, as in ``Trainer.step(batch_size)``.
    stacked_inputs : bool
        When True every batch NDArray carries a leading ``(K, ...)`` axis
        and each inner step consumes one slice (distinct data per step);
        when False (default) the batch is a per-execution constant every
        inner step reuses — the synthetic-bench shape.  Explicit, not
        inferred: a batch axis that happens to equal K must not silently
        change semantics.

    Calling the instance with the batch NDArrays runs K steps on device
    and returns an NDArray of shape ``(K,)`` holding each inner step's
    summed loss (the scalar the tape would have seeded); parameters,
    optimizer state and aux arrays are committed back to the net and
    trainer so eager code sees the updated model.

    Failure safety: the FIRST execution (where trace/compile/OOM
    problems cluster) is validated — state is snapshotted, the result
    hard-synced, and everything restored if it fails, so the caller can
    fall back to per-step ``Trainer.step`` with the model intact.
    Steady-state executions skip the snapshot (the fused program
    donates its buffers; per-call copies would defeat the point), so a
    mid-training backend loss poisons parameters exactly as any
    donated jit program would — checkpoint periodically at scale.
    """

    def __init__(self, net, trainer, forward_loss, steps_per_execution=8,
                 batch_size=1, stacked_inputs=False):
        if steps_per_execution < 1:
            raise MXNetError("steps_per_execution must be >= 1")
        self.stacked_inputs = bool(stacked_inputs)
        self.net = net
        self.trainer = trainer
        self.forward_loss = forward_loss
        self.k = int(steps_per_execution)
        self.batch_size = int(batch_size)
        self._jit_cache = {}
        # the fused program donates the live weight/state buffers, so a
        # failure during the FIRST execution of each signature (where
        # trace, compile and fit problems cluster — a new batch shape is
        # a new compile) must not leave the model poisoned: that call
        # snapshots device copies, hard-syncs the result, and restores
        # everything on any failure.  Steady-state calls skip the
        # snapshot (per-call copies would defeat the optimization); a
        # failure there — a died backend — poisons params like any
        # donated jit program would.
        self._validated_sigs = set()
        self._dispatches = 0   # __call__ count: the lane log's ``seq``
        #: what the forward reported in the newest dispatch, name ->
        #: NDArray (K, ...) on the device; facts of the trace, name -> value
        self.reported, self.facts = {}, {}
        self._unfetched = []   # (lane record, reported raws), oldest first

        optzr = trainer._optimizer
        if type(optzr)._step is opt.Optimizer._step:
            raise MXNetError(
                f"optimizer {type(optzr).__name__} has no pure _step rule; "
                "FusedTrainStep needs the functional update path")
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._update_on_kvstore:
            raise MXNetError(
                "FusedTrainStep cannot fuse update_on_kvstore modes (the "
                "store owns the update); use per-step Trainer.step")
        kv = trainer._kvstore
        if kv is not None:
            # the fused program applies RAW per-host gradients: any store
            # that reduces across workers (dist_tpu_sync all_sum) or
            # rewrites gradients (2-bit compression residuals) would be
            # silently skipped — params diverge with no error.  A local
            # single-worker store's push/pull is identity, so only that
            # is fusable.
            import jax

            dist = str(getattr(kv, "type", "")).startswith("dist") or \
                getattr(kv, "num_workers", 1) > 1 or jax.process_count() > 1
            if dist or trainer._compression_params:
                raise MXNetError(
                    "FusedTrainStep cannot fuse distributed or "
                    "gradient-compressing kvstore paths (the fused program "
                    "skips allreduce/compression); use per-step "
                    "Trainer.step")

        from ..ndarray import sparse as sp

        self._live = []          # indices into trainer._params to update
        self._aux_params = []    # grad_req == 'null' params (BN stats...)
        for i, param in enumerate(trainer._params):
            if param._data is None:
                if param._deferred_init is not None:
                    raise MXNetError(
                        f"parameter {param.name} has unresolved deferred "
                        "shape: run one forward before fusing")
                raise MXNetError(
                    f"parameter {param.name} was not initialized")
            if param.grad_req == "null":
                self._aux_params.append(param)
                continue
            if param._grad_stype != "default":
                raise MXNetError(
                    f"parameter {param.name} has sparse grad "
                    f"({param._grad_stype}); not fusable")
            self._live.append(i)
        if isinstance(getattr(optzr, "rescale_grad", 1.0), sp.BaseSparseNDArray):
            raise MXNetError("sparse rescale_grad not supported")

    # -- pure step body ------------------------------------------------------
    def _pure_loss(self, w_raws, aux_raws, x_raws, key):
        """(trainable raws, aux raws, input raws, key) ->
        (summed-loss scalar, new aux raws).  Same handle-substitution
        trick as ``_CachedGraph._pure`` (gluon/block.py)."""
        from .. import random as mxrand

        trainer = self.trainer
        w_handles = [trainer._params[i]._data for i in self._live]
        aux_handles = [p._data for p in self._aux_params]
        saved_w = [h._data for h in w_handles]
        saved_aux = [h._data for h in aux_handles]
        try:
            for h, r in zip(w_handles, w_raws):
                h._data = r
            for h, r in zip(aux_handles, aux_raws):
                h._data = r
            args = [NDArray(r) for r in x_raws]
            with ag._RecordingStateScope(False, True), \
                    mxrand.key_provider(key), _trace_guard(), \
                    reported() as side:
                loss = self.forward_loss(self.net, *args)
            import jax

            leaves = jax.tree_util.tree_leaves(
                loss, is_leaf=lambda x: isinstance(x, NDArray))
            total = sum(l._data.astype(np.float32).sum() for l in leaves)
            new_aux = tuple(h._data for h in aux_handles)
            self.facts = {n: v for n, v in side.items()
                          if not isinstance(v, NDArray)}
            values = {n: jax.lax.stop_gradient(v._data)
                      for n, v in sorted(side.items())
                      if isinstance(v, NDArray)}
            return total, (new_aux, values)
        finally:
            for h, s in zip(w_handles, saved_w):
                h._data = s
            for h, s in zip(aux_handles, saved_aux):
                h._data = s

    def _build(self, mp_flags):
        """Trace the K-step program.  With ``stacked_inputs`` each scan
        iteration consumes one (K, ...) slice; otherwise the whole batch
        is a per-execution constant closed over by the body.  lr/wd
        enter as traced vectors so LR schedules don't retrace."""
        import jax

        optzr = self.trainer._optimizer
        k = self.k
        stacked_inputs = self.stacked_inputs
        # baked at build time; the compile signature keys on it, so each
        # numerics mode keeps one K-step program
        numerics_on = _numerics.trace_enabled()
        grad_and_aux = jax.value_and_grad(self._pure_loss, argnums=0,
                                          has_aux=True)

        def one_step(carry, xr, consts, lr_v, wd_v):
            w, m, s, aux, t, key = carry
            key, sub = jax.random.split(key)
            x_raws = list(xr) if stacked_inputs else list(consts)
            (loss_sum, (new_aux, values)), grads = grad_and_aux(
                list(w), list(aux), x_raws, sub)
            # same traced update contract as the Trainer's fused
            # multi-tensor path (optimizer._fused_param_updates)
            new_w, new_m, new_s = opt._fused_param_updates(
                optzr, mp_flags, w, m, grads, s, lr_v, wd_v, t)
            nstats = tuple(
                (_numerics.stats_of(g), _numerics.stats_of(nw - ow))
                for g, nw, ow in zip(grads, new_w, w)) \
                if numerics_on else ()
            return ((new_w, new_m, new_s, new_aux, t + 1, key),
                    (loss_sum, nstats, values))

        def _reduce_k(st):
            # per-param stats stacked (K,) by the scan, folded to one
            # bundle per execution INSIDE the compile: overflow counts
            # sum over the K inner steps, magnitudes keep the freshest
            # (l2/mean last, maxabs worst-case)
            import jax.numpy as jnp

            return {"l2": st["l2"][-1], "maxabs": jnp.max(st["maxabs"]),
                    "mean": st["mean"][-1], "nan": jnp.sum(st["nan"]),
                    "inf": jnp.sum(st["inf"])}

        def k_steps(w, m, s, aux, t, key, lr_v, wd_v, consts, stacked):
            def body(carry, xr):
                return one_step(carry, xr, consts, lr_v, wd_v)

            carry, (losses, nstats, values) = jax.lax.scan(
                body, (w, m, s, aux, t, key), stacked,
                length=(None if stacked_inputs else k))
            nstats = tuple((_reduce_k(g), _reduce_k(u))
                           for g, u in nstats)
            return carry[:5], losses, nstats, values

        # donate weights/masters/states/aux: K steps of updates in place
        return jax.jit(k_steps, donate_argnums=(0, 1, 2, 3))

    # -- dispatch ------------------------------------------------------------
    def _prepare(self, batch):
        """Assemble one execution of the K-step program from the live
        trainer state: -> (sig, fn, mp_flags, masters, head, tail) with
        ``fn(*head, key, *tail)`` the call.  Builds (never runs) the
        jitted program on a signature's first sighting."""
        import jax.numpy as jnp

        trainer = self.trainer
        optzr = trainer._optimizer
        optzr.rescale_grad = trainer._scale / self.batch_size

        weights, states, masters = [], [], []
        lrs, wds, ts, mp_flags = [], [], [], []
        for i in self._live:
            trainer._init_states(i)
            param = trainer._params[i]
            state = trainer._states[i]
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
            lrs.append(float(optzr._get_lr(i)))
            wds.append(float(optzr._get_wd(i)))
            # t for the FIRST inner step, without mutating the optimizer:
            # a failed trace/dispatch must leave the trainer's update
            # counts exactly as the eager fallback expects them
            ts.append(optzr._index_update_count.get(
                i, optzr.begin_num_update) + 1)

        if self.stacked_inputs:
            for b in batch:
                if b.ndim < 1 or b.shape[0] != self.k:
                    raise MXNetError(
                        f"stacked_inputs=True requires every batch leaf "
                        f"to lead with K={self.k}, got shape {b.shape}")
        from .. import parallel

        mesh = parallel.current_mesh()
        # same shapes under a different mesh are a different program
        # (GSPMD collectives, per-device tiling) — key the compile cache
        # and the cost registry per mesh
        mesh_sig = None if mesh is None else tuple(mesh.shape.items())
        sig = (type(optzr).__name__, float(optzr.rescale_grad),
               tuple(mp_flags),
               tuple((b.shape, str(b.dtype)) for b in batch), mesh_sig,
               _numerics.signature())
        fn = self._jit_cache.get(sig)
        if fn is None:
            telemetry.count("step_fusion.cache_miss")
            if _retrace._enabled:
                # registered compile site: named components so a
                # post-warmup retrace says exactly what diverged
                # (closure attrs like rescale_grad included)
                _retrace.observe(
                    "step_fusion", id(self),
                    {"optimizer": sig[0], "rescale_grad": sig[1],
                     "mp_flags": sig[2], "batch": sig[3], "mesh": sig[4],
                     "numerics": sig[5]},
                    site="mxnet_tpu.gluon.step_fusion:"
                         "FusedTrainStep.__call__")
            with telemetry.span("step_fusion.build"):
                fn = self._build(tuple(mp_flags))
            self._jit_cache[sig] = fn

        w_raws = tuple(w._data for w in weights)
        m_raws = tuple(m._data for m in masters if m is not None)
        s_raws = tuple(tuple(s._data for s in ss) for ss in states)
        aux_raws = tuple(p._data._data for p in self._aux_params)
        t_v = jnp.asarray(ts, jnp.int32)
        lr_v = jnp.asarray(lrs, jnp.float32)
        wd_v = jnp.asarray(wds, jnp.float32)
        consts = () if self.stacked_inputs else \
            tuple(b._data for b in batch)
        stacked = tuple(b._data for b in batch) if self.stacked_inputs \
            else None
        return (sig, fn, mp_flags, masters,
                (w_raws, m_raws, s_raws, aux_raws, t_v),
                (lr_v, wd_v, consts, stacked))

    def lower(self, *batch):
        """The K-step program for ``batch`` as a ``jax.stages.Lowered``,
        traced from the live trainer state without running or donating
        anything — for reading what the step compiles to (e.g. whether
        the attention kernel is in it)."""
        import jax

        from ..ops.registry import dispatch_platform, platform_of_raws

        _sig, fn, _mp, _masters, head, tail = self._prepare(batch)
        with dispatch_platform(platform_of_raws(head[0])):
            return fn.lower(*head, jax.random.PRNGKey(0), *tail)

    def __call__(self, *batch):
        from .. import engine as _engine

        if _engine._bulk_on:
            _engine.flush("dispatch")

        t0 = time.perf_counter()
        self._dispatches += 1
        with TraceAnnotation("mxt.train.dispatch", seq=self._dispatches,
                             k=self.k):
            return self._dispatch(batch, t0)

    def _dispatch(self, batch, t0):
        """The body of :meth:`__call__`, stamped for the lane log's
        ``train.dispatch`` record: ``t0`` entry, ``t_args`` operands
        ready (cache lookup, flatten, rates, key, first-call snapshot),
        ``t_disp1`` jitted call returned, ``t_end`` results committed."""
        trainer = self.trainer
        optzr = trainer._optimizer
        sig, fn, mp_flags, masters, head, tail = self._prepare(batch)
        w_raws, m_raws, s_raws, aux_raws, _t_v = head

        from .. import random as mxrand

        key = mxrand.next_key()

        snapshot = None if sig in self._validated_sigs else \
            self._snapshot(w_raws)
        t_args = time.perf_counter()
        telemetry.gauge("step_fusion.steps_per_execution", self.k)
        telemetry.count("step_fusion.steps", self.k)
        if _costs._enabled:
            # registered BEFORE the donating dispatch: lower() reads only
            # avals, so the (about-to-be-donated) buffers are never touched
            pol = _mem_policy_tier()
            _costs.note("step_fusion", (id(self), sig), fn,
                        (*head, key, *tail), remat=pol,
                        site="mxnet_tpu.gluon.step_fusion:"
                             "FusedTrainStep.__call__")
        try:
            # publish the operands' platform so platform-conditional ops
            # (pallas flash) route correctly inside the fused trace even
            # in a mixed-platform process
            from ..ops.registry import dispatch_platform, platform_of_raws

            # first execution per signature traces + compiles the K-step
            # program (and hard-syncs for validation); steady state is a
            # single async replay dispatch per K steps
            with telemetry.span("step_fusion.compile" if snapshot is not None
                                else "step_fusion.replay"), \
                    dispatch_platform(platform_of_raws(w_raws)):
                (new_w, new_m, new_s, new_aux, _new_t), losses, nstats, \
                    values = fn(*head, key, *tail)
            t_disp1 = time.perf_counter()

            if _san._enabled:
                # weights/masters/states/aux were donated at dispatch;
                # poison the old buffers so stale views raise with this
                # site.  The commit below rebinds every live holder to
                # the result buffers, which clears the poison for them.
                _san.donate(self._donated_raws(w_raws, m_raws, s_raws,
                                               aux_raws),
                            self._donation_site())
            if _mw._enabled:
                # the device freed the donated buffers at dispatch even
                # though python aliases may linger — release them now
                _mw.donated(self._donated_raws(w_raws, m_raws, s_raws,
                                               aux_raws))
            opt._commit_param_updates(trainer, self._live, mp_flags,
                                      masters, new_w, new_m, new_s)
            if nstats:
                # K-reduced grad/update-delta bundles, still device
                # scalars — queued for the stride harvest, no host sync
                names, stats = [], []
                for i, (gs, us) in zip(self._live, nstats):
                    pname = trainer._params[i].name
                    names += ["grad." + pname, "update." + pname]
                    stats += [gs, us]
                _numerics.record_compiled(names, stats)
            for i in self._live:
                optzr._index_update_count[i] = \
                    optzr._index_update_count.get(
                        i, optzr.begin_num_update) + self.k
                optzr.num_update = max(optzr.num_update,
                                       optzr._index_update_count[i])
            for p, raw in zip(self._aux_params, new_aux):
                p._data._data = raw
            if snapshot is not None:
                # force TRUE completion before declaring the program
                # safe: dispatch is async and a runtime failure (OOM)
                # surfaces only at a blocking wait.  block_until_ready
                # waits WITHOUT copying the buffer to host (np.asarray
                # would add a device->host transfer to the stall).
                losses.block_until_ready()  # mxlint: allow=T1
                self._validated_sigs.add(sig)
                telemetry.count("step_fusion.compile")
            rec = tracing.lane_record(
                "train.dispatch", path="fused", seq=self._dispatches,
                k=self.k, compiled=snapshot is not None, t0=t0,
                t_args=t_args, t_disp1=t_disp1,
                t_end=time.perf_counter())
            if snapshot is not None:
                rec.update(self.facts)
            self.reported = {n: NDArray(v) for n, v in values.items()}
            if values:
                self._unfetched.append((rec, values))
                del self._unfetched[:-self._KEPT_UNFETCHED]
            return NDArray(losses)
        except Exception as exc:
            if snapshot is not None:
                self._restore(snapshot)
            elif _san._enabled:
                # steady state: the signature was validated, so the
                # program was compiled and the failure happened at (or
                # after) dispatch — the donated buffers are gone and the
                # model is poisoned exactly as documented above.  Record
                # it so every later read names this site instead of
                # XLA's deleted-array error.
                _san.donate(self._donated_raws(w_raws, m_raws, s_raws,
                                               aux_raws),
                            self._donation_site() + " [failed execution]")
            if _mw._enabled:
                _mw.annotate_oom(exc, context="FusedTrainStep dispatch")
            raise

    #: dispatches whose reported values wait for ``fetch_reported``; a
    #: caller that never fetches holds no more than these
    _KEPT_UNFETCHED = 4

    def fetch_reported(self):
        """The reported values of the oldest dispatch not yet fetched, as
        host arrays stacked ``(K, ...)``, or None when there is none:
        called where the losses are fetched, one dispatch behind.  A value
        of one number a step is also written into that dispatch's
        ``train.dispatch`` lane record, a list of K."""
        if not self._unfetched:
            return None
        import jax

        rec, values = self._unfetched.pop(0)
        host = jax.device_get(values)  # mxlint: allow=T1
        rec.update({n: [float(x) for x in v] for n, v in host.items()
                    if v.ndim == 1})
        return host

    def free_grad_buffers(self):
        """Release the eager gradient buffers of the trained parameters
        (``attach_grad``'s zeros, 4 bytes a float32 parameter): the fused
        program computes its gradients inside and never writes them.
        ``Parameter.grad()`` and the per-step path then need
        ``Parameter.zero_grad()``'s buffer back (``attach_grad``)."""
        for i in self._live:
            self.trainer._params[i]._data._grad = None

    def _donated_raws(self, w_raws, m_raws, s_raws, aux_raws):
        return w_raws + m_raws + \
            tuple(r for ss in s_raws for r in ss) + aux_raws

    def _donation_site(self):
        return ("FusedTrainStep.__call__ (gluon/step_fusion.py, "
                f"K={self.k} fused train step, donate_argnums=(0, 1, 2, 3))")

    # -- first-call safety ---------------------------------------------------
    def _snapshot(self, w_raws):
        """Copies of everything the program donates, for ``_restore``.
        On the device where they fit beside the originals; else on the
        host (a model sized to its chip: the first call then pays two
        transfers instead of failing for want of room)."""
        import jax
        import jax.numpy as jnp

        trainer = self.trainer
        optzr = trainer._optimizer
        held = [p._data._data for p in trainer._params
                if p._data is not None]
        held += [h._data for s in trainer._states if s is not None
                 for h in opt._flatten_state(s)]
        need = sum(a.size * a.dtype.itemsize for a in held)
        stats = None
        try:
            stats = next(iter(w_raws[0].devices())).memory_stats()
        except (AttributeError, IndexError, StopIteration, RuntimeError):
            pass
        room = None if not stats or "bytes_limit" not in stats else \
            stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        if room is not None and need > 0.5 * room:
            telemetry.count("step_fusion.snapshot_on_host")
            def copy(a):
                return np.asarray(jax.device_get(a))  # mxlint: allow=T1
        else:
            copy = jnp.array
        params = [(p, copy(p._data._data)) for p in trainer._params
                  if p._data is not None]
        state_raws = [
            None if s is None else
            [(h, copy(h._data)) for h in opt._flatten_state(s)]
            for s in trainer._states]
        aux = [(p, copy(p._data._data)) for p in self._aux_params]
        return (params, state_raws, list(trainer._states),
                list(trainer._states_initialized), aux,
                dict(optzr._index_update_count), optzr.num_update)

    def _restore(self, snapshot):
        (params, state_raws, states, inited, aux, counts,
         num_update) = snapshot
        trainer = self.trainer
        optzr = trainer._optimizer
        import jax.numpy as jnp

        for p, raw in params:
            p._data._data = jnp.asarray(raw)      # a host copy goes back
        for entry in state_raws:
            if entry:
                for h, raw in entry:
                    h._data = jnp.asarray(raw)
        trainer._states[:] = states
        trainer._states_initialized[:] = inited
        for p, raw in aux:
            p._data._data = jnp.asarray(raw)
        optzr._index_update_count.clear()
        optzr._index_update_count.update(counts)
        optzr.num_update = num_update

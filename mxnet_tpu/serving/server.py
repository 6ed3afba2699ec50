"""The user-facing serving surface: configs, servers, lifecycle.

Two server classes over one contract (bounded queue → scheduler thread
→ per-request futures, docs/serving.md):

* :class:`InferenceServer` — stateless models (one forward per
  request): a ``Predictor`` (the MXPredCreate surface), a hybridized
  gluon block (e.g. BERT), or any callable.  Dynamic batching with
  power-of-two batch/length buckets.
* :class:`GenerativeServer` — decode with the paged cache for any
  model that gives the engine a decoder (``serving_decoder(max_len)``:
  the paged step and prefill programs) and a cache spec (which layers
  keep K/V blocks, which a per-slot state): requests join and leave
  the in-flight decode batch between steps (continuous batching).

``ServerConfig(int8=True)`` applies weight quantization at load time:
gluon blocks go through ``contrib.quantization.quantize_net`` (needs
``calib_data``); the llama engine uses weight-only per-channel int8.

Synchronous convenience: ``server.infer(...)`` / ``server.generate(...)``
submit and wait (the future's ``result()`` is the sanctioned eager wait,
same contract as async-checkpoint tickets).
"""
from __future__ import annotations

import time

import numpy as np

from .. import telemetry
from ..telemetry import capacity
from ..telemetry import tracing
from ..base import MXNetError
from .bucketing import BucketPolicy
from .protocol import Request, ServerClosedError, ServerOverloadedError
from .scheduler import BatchScheduler, RequestQueue

__all__ = ["ServerConfig", "InferenceServer", "GenerativeServer"]

#: the stretch of the lane log that ``GenerativeServer.stats()`` looks
#: through for stalled turns, seconds back from now
STALLS_VIEW_S = 60.0


class ServerConfig:
    """Knobs shared by both servers (defaults are test-scale).

    ``max_batch``/``max_length`` bound the bucket grid — the compiled-
    signature ceiling is ``len(batch_buckets) × len(length_buckets)``.
    ``queue_capacity`` bounds admission (beyond it, submit raises
    ``ServerOverloadedError``).  ``length_axis`` names the bucketed
    axis of each request's input arrays; ``output_length_axis`` (may be
    None) the per-example output axis to trim back at demux.
    ``num_slots`` (generative) is the KV-cache capacity = max
    concurrent sequences; ``int8`` switches on load-time weight
    quantization."""

    def __init__(self, max_batch=8, max_length=128, min_batch=1,
                 min_length=8, queue_capacity=64, batch_window_ms=2.0,
                 summary_every=32, length_axis=0, output_length_axis=None,
                 num_slots=4, max_new_tokens=32, int8=False,
                 calib_data=None, kv_mode="paged", block_size=16,
                 num_blocks=None, http_port=None, http_host="127.0.0.1",
                 slo=None, slo_window=256, draft_net=None, spec_k=3,
                 radix_cache=False, prefix_cache_tokens=None):
        self.policy = BucketPolicy(max_batch=max_batch,
                                   max_length=max_length,
                                   min_batch=min_batch,
                                   min_length=min_length)
        self.queue_capacity = int(queue_capacity)
        self.batch_window_ms = float(batch_window_ms)
        self.summary_every = int(summary_every)
        self.length_axis = int(length_axis)
        self.output_length_axis = output_length_axis
        self.num_slots = int(num_slots)
        self.max_new_tokens = int(max_new_tokens)
        self.int8 = bool(int8)
        self.calib_data = calib_data
        # generative KV storage is the block pool under the prefill and
        # decode lanes.  ``kv_mode`` once chose between it and a slot
        # ledger: the keyword is taken for its callers' sake and not
        # kept.  ``num_blocks=None`` sizes the pool at num_slots ×
        # max_len tokens; smaller pools bound capacity by tokens in
        # flight instead.
        if kv_mode != "paged":
            raise MXNetError(
                f"kv_mode={kv_mode!r}: the slot-ledger mode is gone; "
                "the server keeps K/V in the paged block pool "
                "(kv_mode='paged', the one value still taken)")
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        # observability (r12): ``http_port`` starts the live metrics
        # endpoint with the server (0 = ephemeral port, read it back
        # from ``server.metrics_url``); ``slo`` maps tenant →
        # {"ttft_ms": x, "tpot_ms": y} targets (a flat dict is the
        # "default" tenant) for goodput accounting over ``slo_window``
        # recent requests (docs/observability.md).
        self.http_port = http_port if http_port is None else int(http_port)
        self.http_host = str(http_host)
        self.slo = slo
        self.slo_window = int(slo_window)
        # speculative decoding + radix prefix cache (r19):
        # ``draft_net`` switches speculation on (the small proposer
        # model; ``spec_k`` proposals per slot per verify), and
        # ``radix_cache`` turns on prompt-prefix KV reuse with an LRU
        # budget of ``prefix_cache_tokens`` (None = half the pool).
        self.draft_net = draft_net
        self.spec_k = int(spec_k)
        self.radix_cache = bool(radix_cache)
        self.prefix_cache_tokens = prefix_cache_tokens \
            if prefix_cache_tokens is None else int(prefix_cache_tokens)


class _ServerBase:
    """start/stop/context-manager scaffolding shared by both servers,
    plus the r12 observability surface: the metrics endpoint lifecycle,
    the shared SLO tracker, and trace creation at submit."""

    def __init__(self, config):
        self.config = config or ServerConfig()
        self.queue = RequestQueue(self.config.queue_capacity)
        self._running = False
        self._metrics = None
        self.slo = None
        if self.config.slo:
            from .metrics import SLOTracker

            self.slo = SLOTracker(self.config.slo,
                                  window=self.config.slo_window)

    def start(self):
        self._sched.start()
        self._running = True
        self._start_http()
        return self

    def stop(self, drain=True):
        """Graceful by default: queued work is served before exit."""
        if not self._running:
            return
        self._running = False
        self._stop_http()
        self._sched.stop(drain=drain)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- metrics endpoint -----------------------------------------------------
    def _start_http(self):
        if self.config.http_port is None or self._metrics is not None:
            return
        from .metrics import MetricsServer

        self._metrics = MetricsServer(
            self, host=self.config.http_host,
            port=self.config.http_port).start()

    def _stop_http(self):
        if self._metrics is not None:
            self._metrics.stop()
            self._metrics = None

    @property
    def metrics_url(self):
        """Base URL of the live endpoint (None when not started)."""
        return self._metrics.url if self._metrics is not None else None

    def metrics_gauges(self):
        """Live gauges the /metrics scrape adds on top of the telemetry
        snapshot (subclasses extend)."""
        return {"serving.queue_depth": len(self.queue),
                "serving.rejected_total": self.queue.rejected}

    # -- submission -----------------------------------------------------------
    def _submit(self, req):
        if not self._running:
            raise ServerClosedError("server is not running; call start()")
        if tracing.is_enabled() and req.trace is None:
            req.trace = tracing.start_trace(request_id=req.id,
                                            tenant=req.tenant)
        try:
            self.queue.put(req)
        except ServerOverloadedError as exc:
            # shed-load accounting: the rejected request still lands in
            # the JSONL stream (tagged) and trips the flight recorder
            telemetry.emit(req.record(lane="queue", status="rejected",
                                      error=repr(exc)))
            if req.trace is not None:
                tracing.finish(req.trace, status="rejected", lane="queue",
                               error=repr(exc), request_id=req.id)
                req.trace = None
            tracing.incident("overload_rejection", context={
                "queue_capacity": self.queue.capacity,
                "rejected": self.queue.rejected})
            raise
        # the stamps (t_start, t_first, first_tick, ...) stay readable
        # from the handle the caller holds
        req.future.request = req
        return req.future


class InferenceServer(_ServerBase):
    """Dynamic-batching server for stateless models.

    ``model`` may be a ``Predictor``, a gluon block, or a callable
    taking a dict of stacked numpy arrays and returning outputs.
    ``input_names`` orders multi-input models (defaults to the
    Predictor's own input names, or ``["data"]``).
    """

    def __init__(self, model, config=None, input_names=None):
        super().__init__(config)
        self.model = model
        self._predictor = model if hasattr(model, "forward") and \
            hasattr(model, "input_names") else None
        if input_names is None:
            input_names = self._predictor.input_names \
                if self._predictor is not None else ["data"]
        self.input_names = list(input_names)
        if self.config.int8 and self._predictor is None and \
                hasattr(model, "collect_params"):
            from ..contrib.quantization import quantize_net

            if self.config.calib_data is None:
                raise MXNetError(
                    "int8 block serving needs config.calib_data for "
                    "calibration")
            self.model = quantize_net(model,
                                      calib_data=self.config.calib_data,
                                      calib_mode="naive")
        self._sched = BatchScheduler(
            self._run_batch, self.config.policy, self.queue,
            length_axis=self.config.length_axis,
            output_length_axis=self.config.output_length_axis,
            batch_window_ms=self.config.batch_window_ms,
            summary_every=self.config.summary_every)

    def _run_batch(self, batch):
        """One padded bucket through the model (scheduler thread)."""
        from .. import ndarray as nd

        if self._predictor is not None:
            return self._predictor.forward(**batch)
        if callable(self.model) and not hasattr(self.model,
                                                "collect_params"):
            return self.model(batch)
        args = [nd.array(batch[n]) for n in self.input_names]
        out = self.model(*args)
        return out if isinstance(out, (list, tuple)) else [out]

    # -- client surface -------------------------------------------------------
    def submit(self, inputs, length=None, tenant=None):
        """Async: one example's inputs (array, or dict name → array) →
        a Future resolving to the demuxed output(s).  ``length`` is the
        true size of the bucketed axis (defaults to the first input's
        ``length_axis`` extent)."""
        if not isinstance(inputs, dict):
            inputs = {self.input_names[0]: inputs}
        inputs = {k: np.asarray(v) for k, v in inputs.items()}
        if length is None:
            length = inputs[self.input_names[0]] \
                .shape[self.config.length_axis]
        req = Request(inputs=inputs, length=int(length), tenant=tenant)
        return self._submit(req)

    def infer(self, inputs, length=None, timeout=60.0):
        """Sync: submit + wait."""
        return self.submit(inputs, length=length).result(timeout)

    def health(self):
        """The /healthz body: scheduler-thread liveness + queue depth
        (host-side snapshot, never a device touch)."""
        alive = self._sched._thread is not None \
            and self._sched._thread.is_alive()
        if not self._running:
            status = "stopped"
        else:
            status = "ok" if alive else "degraded"
        return {"status": status, "running": self._running,
                "scheduler_alive": alive,
                "queue_depth": len(self.queue),
                "rejected": self.queue.rejected}

    def in_flight(self):
        """The /requests table: currently queued requests."""
        with self.queue._cond:
            items = list(self.queue._items)
        now = time.perf_counter()
        return [{"request_id": r.id, "state": "queued",
                 "length": r.length, "tenant": r.tenant,
                 "trace_id": r.trace.trace_id
                 if r.trace is not None else None,
                 "age_ms": round((now - r.t_submit) * 1e3, 3)}
                for r in items]

    def stats(self):
        """Server + compile-cache counters (the bucketing-policy
        verification surface)."""
        out = {
            "completed": self._sched.completed,
            "failed": self._sched.failed,
            "batches": self._sched.batches,
            "rejected": self.queue.rejected,
            "pending": len(self.queue),
            "signature_ceiling": len(self.config.policy.signatures()),
        }
        if self._predictor is not None:
            out["cache"] = self._predictor.cache_stats()
        elif hasattr(self.model, "_cached_op") and \
                self.model._cached_op is not None:
            out["cache"] = self.model._cached_op.cache_stats()
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        return out


def _split_mesh(mesh, dp_axis="dp"):
    """One submesh per dp replica: slice ``dp_axis`` off and keep the
    remaining axes (tp, ...) per slice, so each replica's engine is an
    ordinary tensor-parallel engine over its own devices.  No mesh →
    ``[None]`` (single default-device replica); no dp axis → the whole
    mesh is one replica."""
    if mesh is None:
        return [None]
    if dp_axis not in mesh.axis_names:
        return [mesh]
    from jax.sharding import Mesh

    axis = mesh.axis_names.index(dp_axis)
    rest = tuple(a for a in mesh.axis_names if a != dp_axis)
    devs = np.moveaxis(mesh.devices, axis, 0)
    if not rest:
        # dp-only mesh: each replica is a single-device tp=1 mesh so
        # its weights still commit to ITS device, not the default one
        return [Mesh(np.asarray(devs[i]).reshape(1), ("tp",))
                for i in range(devs.shape[0])]
    return [Mesh(devs[i], rest) for i in range(devs.shape[0])]


class GenerativeServer(_ServerBase):
    """Continuous-batching decode server for a causal LM that answers
    ``serving_decoder(max_len)`` (``LlamaForCausalLM``,
    ``Lfm2MoeForCausalLM``); the engine asks it for the decoder's paged
    programs and its cache spec, the lanes for nothing.

    Mesh-native: ``mesh=`` places the weights (and the KV pool)
    tensor-parallel per ``partition_rules=`` (default: the
    ``"llama_serving"`` family table) exactly like ``Trainer`` does for
    training; a ``dp`` mesh axis runs one independent replica per dp
    slice behind this one front queue, routed least-loaded by
    :class:`~.lanes.ReplicaDispatcher`.  K/V lives in the paged block
    pool, under disaggregated prefill/decode lanes.
    """

    def __init__(self, net, config=None, mesh=None, partition_rules=None):
        super().__init__(config)
        from .lanes import Replica, ReplicaDispatcher

        cfg = self.config
        self.mesh = mesh
        self._replicas = [
            Replica(net, cfg.policy, index=i, mesh=sub,
                    partition_rules=partition_rules,
                    num_slots=cfg.num_slots, int8=cfg.int8,
                    block_size=cfg.block_size, num_blocks=cfg.num_blocks,
                    queue_capacity=cfg.queue_capacity,
                    summary_every=cfg.summary_every, slo=self.slo,
                    draft_net=cfg.draft_net, spec_k=cfg.spec_k,
                    radix_cache=cfg.radix_cache,
                    prefix_cache_tokens=cfg.prefix_cache_tokens)
            for i, sub in enumerate(_split_mesh(mesh))]
        self._dispatcher = ReplicaDispatcher(self.queue, self._replicas)
        self.engine = self._replicas[0].engine

    @property
    def replicas(self):
        return self._replicas

    # -- lifecycle ------------------------------------------------------------
    def start(self):
        # fresh ledgers per server lifetime: replica indices restart at
        # 0, so a previous server's estimators must not leak in
        capacity.reset()
        for rep in self._replicas:
            rep.start()
        self._dispatcher.start()
        self._running = True
        self._start_http()
        return self

    def stop(self, drain=True):
        if not self._running:
            return
        self._running = False
        self._stop_http()
        # flush the front queue into the replicas first, then drain
        # each replica (prefill lane before decode lane)
        self._dispatcher.stop(drain=drain)
        for rep in self._replicas:
            rep.stop(drain=drain)

    # -- client surface -------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=None, tenant=None):
        """Async: 1-D prompt token ids → Future resolving to the full
        sequence (prompt + generated), greedy decode.  ``tenant`` keys
        the request's SLO targets (config.slo).  The future carries its
        :class:`~.protocol.Request` as ``.request`` (id and stamps)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        n = int(max_new_tokens or self.config.max_new_tokens)
        if n < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if len(prompt) + n > self.engine.max_len:
            raise MXNetError(
                f"prompt {len(prompt)} + {n} new tokens exceeds the "
                f"engine's max_len {self.engine.max_len}")
        req = Request(prompt_ids=prompt, max_new_tokens=n, tenant=tenant)
        req.length = len(prompt)
        return self._submit(req)

    def generate(self, prompt_ids, max_new_tokens=None, timeout=120.0):
        """Sync: submit + wait for the full sequence."""
        return self.submit(prompt_ids, max_new_tokens).result(timeout)

    # -- observability surface ------------------------------------------------
    def health(self):
        """The /healthz body: per-replica lane liveness, queue depths,
        and KV occupancy/fragmentation — every number a host-side
        counter read, never a device touch.  ``status`` is ``"ok"``
        only when every lane thread is alive."""
        reps = []
        all_alive = True
        any_saturated = False
        for r in self._replicas:
            kv = r.mgr.stats()
            pa, da = r.prefill.alive(), r.decode.alive()
            all_alive = all_alive and pa and da
            row = {
                "replica": r.index,
                "prefill_alive": pa,
                "decode_alive": da,
                "queue_depth": len(r.queue),
                "in_flight": kv["occupancy"],
                "failed": r.failed,
                "kv_utilization": kv["utilization"],
                "kv_fragmentation": kv["fragmentation"],
                "kv_blocks_in_use": kv["blocks_in_use"]}
            cap = capacity.snapshot(r.index)
            if cap is not None:
                row["saturated"] = cap["saturated"]
                row["rho"] = cap["rho"]
                row["headroom_rps"] = cap["headroom_rps"]
                any_saturated = any_saturated or cap["saturated"]
            reps.append(row)
        if not self._running:
            status = "stopped"
        elif not all_alive:
            status = "degraded"
        elif any_saturated:
            # degraded-but-alive: every lane is serving, but ρ sits
            # above threshold — still HTTP 200 (a readiness probe must
            # not kill a replica for being busy; the control plane
            # reads headroom, not liveness)
            status = "saturated"
        else:
            status = "ok"
        return {"status": status, "running": self._running,
                "queue_depth": len(self.queue),
                "rejected": self.queue.rejected,
                "replicas": reps}

    def in_flight(self):
        """The /requests table: every request currently queued (front
        queue + replica queues) or decoding, with ids the trace stream
        can be joined on."""
        now = time.perf_counter()

        def queued(queue, replica=None):
            with queue._cond:
                items = list(queue._items)
            return [{"request_id": r.id, "state": "queued",
                     "replica": replica, "length": r.length,
                     "tenant": r.tenant,
                     "trace_id": r.trace.trace_id
                     if r.trace is not None else None,
                     "age_ms": round((now - r.t_submit) * 1e3, 3)}
                    for r in items]

        rows = queued(self.queue)
        for r in self._replicas:
            rows.extend(queued(r.queue, replica=r.index))
            rows.extend(r.decode.snapshot())
        return rows

    def metrics_gauges(self):
        """Extend the base scrape gauges with live KV-pool state —
        per replica when there are several."""
        out = super().metrics_gauges()
        drafted = accepted = 0
        for r in self._replicas:
            kv = r.mgr.stats()
            tag = f"|replica={r.index}"
            out["serving.kv_occupancy" + tag] = kv["occupancy"]
            out["serving.kv_utilization" + tag] = kv["utilization"]
            out["serving.kv_fragmentation" + tag] = kv["fragmentation"]
            out["serving.kv_blocks_in_use" + tag] = kv["blocks_in_use"]
            out["serving.replica_queue_depth" + tag] = len(r.queue)
            if r.spec_k:
                drafted += r.draft_tokens
                accepted += r.accepted_tokens
                if r.draft_tokens:
                    out["serving.accept_rate" + tag] = round(
                        r.accepted_tokens / r.draft_tokens, 4)
            if r.radix is not None:
                rx = r.radix.stats()
                out["serving.radix_hits" + tag] = rx["hits"]
                out["serving.radix_hit_tokens" + tag] = rx["hit_tokens"]
                out["serving.radix_evictions" + tag] = rx["evictions"]
                out["serving.radix_cached_tokens" + tag] = \
                    rx["cached_tokens"]
            cap = capacity.snapshot(r.index)
            if cap is not None:
                out["serving.utilization" + tag] = cap["utilization"]
                out["serving.kv_free_frac" + tag] = cap["kv_free_frac"]
                if cap["rho"] is not None:
                    out["serving.rho" + tag] = cap["rho"]
                if cap["headroom_rps"] is not None:
                    out["serving.headroom_rps" + tag] = \
                        cap["headroom_rps"]
        if drafted:
            out["serving.accept_rate"] = round(accepted / drafted, 4)
        if capacity.is_enabled():
            # fleet-level rollup: worst ρ (the replica closest to the
            # knee governs admission) and total spare request rate
            rhos = [v for k, v in out.items()
                    if k.startswith("serving.rho|")]
            heads = [v for k, v in out.items()
                     if k.startswith("serving.headroom_rps|")]
            utils = [v for k, v in out.items()
                     if k.startswith("serving.utilization|")]
            if rhos:
                out["serving.rho"] = max(rhos)
            if heads:
                out["serving.headroom_rps"] = round(sum(heads), 4)
            if utils:
                out["serving.utilization"] = max(utils)
        return out

    def stats(self):
        reps = self._replicas
        out = {
            "completed": sum(r.completed for r in reps),
            "failed": sum(r.failed for r in reps),
            "decode_steps": sum(r.engine.steps for r in reps),
            # those of the booked steps that were queued before the step
            # ahead of them had been fetched (the lane log's ``ahead``)
            "decode_steps_ahead": sum(r.steps_ahead for r in reps),
            "rejected": self.queue.rejected,
            "pending": len(self.queue) + sum(len(r.queue) for r in reps),
            "kv_cache": reps[0].mgr.stats(),
            "compiled_signatures":
                reps[0].engine.compiled_signatures(),
            "decode_attention": reps[0].engine.decode_attention,
            # "step_kernel" / "step_xla"; None without such layers
            "linear_attention": reps[0].engine.linear_attention,
            "prefill_attention": reps[0].engine.prefill_attention,
            "kv_pack": reps[0].engine.kv_pack,
            "expert_product": reps[0].engine.expert_product,
            "num_replicas": len(reps),
            "kv_layers": reps[0].engine.cache_spec.kv_layers,
            "state_layers": reps[0].engine.cache_spec.state_layers,
            "latent_layers": reps[0].engine.cache_spec.latent_layers,
            # how many times the stack runs a token, what a token keeps
            # in the block tables over every layer and pass, and how much
            # of the pool (in tokens) the requests in flight hold: blocks
            # are granted as a request grows, so these hold tokens
            "cache_passes": reps[0].engine.cache_spec.passes,
            "kv_bytes_per_token": reps[0].engine.kv_bytes_per_token,
            "pool_reserved_tokens": reps[0].mgr.allocator.blocks_in_use
            * reps[0].mgr.block_size,
            "pool_tokens": reps[0].mgr.num_blocks * reps[0].mgr.block_size,
            # the cache's bytes a device, by kind: K/V blocks, per-slot
            # state (and by array where a state layer owns several) and,
            # of a latent model, latent rows and index keys
            "cache_bytes": reps[0].engine.kv_pool_bytes(by_kind=True),
            # "next_token", or "block_diffusion" with the block sizes
            "decoding": reps[0].engine.decoding,
        }
        if reps[0].engine.block is not None:
            out["block_decoding"] = reps[0].engine.block._asdict()
            # passes that stored blocks took, blocks stored and tokens
            # committed, over every tick (the lane log's block_passes /
            # n_store / committed)
            tots = [r.engine.block_totals for r in reps]
            out["blocks"] = {k: sum(t[k] for t in tots) for k in tots[0]}
        if reps[0].engine.cache_spec.expert_layers:
            # what the lane log's experts_touched / expert_rows_max add
            # up to, over every step and prefill program
            tots = [r.engine.expert_totals for r in reps]
            out["experts"] = {
                k: (max if k == "expert_rows_max" else sum)(
                    t[k] for t in tots) for k in tots[0]}
            # (first, count): the part of each layer's bank that this
            # server holds; None: all of it
            out["experts_held"] = reps[0].engine.experts_held
        if len(reps) > 1:
            out["replicas"] = [{
                "completed": r.completed,
                "failed": r.failed,
                "decode_steps": r.engine.steps,
                "kv_cache": r.mgr.stats(),
                "compiled_signatures": r.engine.compiled_signatures(),
                "decode_attention": r.engine.decode_attention,
                "prefill_attention": r.engine.prefill_attention,
            } for r in reps]
        if any(r.spec_k for r in reps):
            drafted = sum(r.draft_tokens for r in reps)
            accepted = sum(r.accepted_tokens for r in reps)
            out["speculative"] = {
                "k": max(r.spec_k for r in reps),
                "draft_tokens": drafted,
                "accepted_tokens": accepted,
                "accept_rate": round(accepted / drafted, 4)
                if drafted else None,
            }
            if drafted:
                telemetry.gauge("serving.accept_rate",
                                out["speculative"]["accept_rate"])
        if any(r.radix is not None for r in reps):
            rx = [r.radix.stats() for r in reps if r.radix is not None]
            out["radix_cache"] = {
                k: sum(s[k] for s in rx)
                for k in ("hits", "misses", "hit_tokens", "evictions",
                          "inserted_blocks", "cached_tokens")}
        telemetry.gauge("serving.kv_occupancy",
                        sum(r.mgr.stats()["occupancy"] for r in reps))
        telemetry.gauge("serving.kv_blocks_in_use",
                        sum(r.mgr.allocator.blocks_in_use for r in reps))
        if capacity.is_enabled():
            out["capacity"] = [capacity.snapshot(r.index) for r in reps]
        # always on: where each prefill lane's wall time went, what
        # growth on demand granted, parked and refused behind it, and the
        # turns of either lane that stalled in the last minute (a bounded
        # stretch: a scrape must not cost what it reports)
        stalled = tracing.stalls(since=time.perf_counter() - STALLS_VIEW_S)
        out["lanes"] = [dict(r.prefill.clock.snapshot(), **r.mgr.growth(),
                             stalls=tracing.stall_totals(
                                 [s for s in stalled
                                  if s["replica"] == r.index]))
                        for r in reps]
        if self.slo is not None:
            out["slo"] = self.slo.snapshot()
        return out

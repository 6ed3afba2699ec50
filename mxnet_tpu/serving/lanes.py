"""Disaggregated prefill/decode execution lanes over paged KV.

`SERVING_LATENCY_r08.json` showed the r8 single-loop server queue-bound
(queue-wait was 52.7 of 53.7 ms closed-loop p99): one thread interleaves
compute-bound prompt prefills with latency-bound decode ticks, so every
long prompt stalls every in-flight decode.  This module splits the two
phases into lanes with their own scheduler threads and batch policies,
connected by an explicit KV handoff:

* :class:`PrefillLane` — batch-tolerant.  Pulls the FIFO-head prompt
  bucket from the replica queue, gated by the paged-KV admission budget
  (free decode slots, free KV blocks, a cumulative prompt-token ceiling
  — prefill batches greedily by token count, not request count), admits
  each request to the :class:`~.kv_cache.PagedKVCacheManager` (which
  takes the prompt's blocks and books the request's whole budget as a
  claim: the request is admitted only if every admitted request can
  still finish, the manager's safe-state rule), runs the prompt forward OUTSIDE the engine's
  device lock, then commits the raw K/V rows into the admitted blocks
  (one brief locked scatter) and hands the slot to the decode lane.
* :class:`DecodeLane` — latency-structured.  Every tick it adopts
  pending handoffs, then advances *its own* slot set one token, each
  slot first granted the block its write lands in
  (:meth:`DecodeLane._grant`: a slot the safe-state rule refuses is
  parked for that step, left out of it and asked about again at the
  next).  It
  never sees a prompt forward: while a long prompt prefills, decode
  ticks keep dispatching (the device lock covers only the KV-mutating
  dispatches, not the prefill compute).  The tick
  (:meth:`DecodeLane._tick`) runs ONE STEP AHEAD of its bookkeeping: a
  turn adopts, queues step K+1 and only then fetches and books step K
  (the engine's ``dispatch_step`` / ``fetch_step``: tokens pass from
  step to step on the device), so the device has a step queued while
  the host books; where the prefill lane has a forward behind step K,
  or is about to (``_prefill_covers``), that forward is what the device
  runs meanwhile and step K+1 is queued behind it.
  For a model that decodes by blocks (the engine's ``block``: what its
  decoder's cache spec says) a step is one pass a slot's block, queued
  ahead the same way (what a block holds, its masks, pass count and
  cursor pass from pass to pass on the device) and booked by
  :meth:`DecodeLane._book_blocks`: 0 to a block's length of tokens
  committed a slot in any order of position, the cursor moved only by
  the pass over a finished block; the prefill lane then hands over no
  first token.  The speculative tick (:meth:`DecodeLane._tick_spec`)
  books its own step.
* :class:`Replica` — one engine + manager + lane pair over one (tp)
  submesh.  A dp mesh axis becomes N independent replicas behind one
  front queue, routed by :class:`ReplicaDispatcher` to the
  least-loaded replica (by reserved + queued tokens).

Host-sync discipline: the decode drain and the handoff boundary block
on device results in :func:`_lane_materialize` ONLY — the lane twin of
``scheduler._materialize``, exempted by name in tools/lint
(``MATERIALIZE_DEFS``); syncs anywhere else in the lanes still flag.

Telemetry: requests carry ``replica``/``handoff_ms``/``kv_blocks`` in
their JSONL records, lanes emit ``serving.prefill`` spans and
``serving.handoff_ms`` histograms, and the decode tick publishes the
``serving.kv_blocks_in_use`` gauge (see docs/observability.md).

Lane log (``telemetry.tracing``, always on): a ``decode.tick`` record a
step of the decode lane, a ``prefill.batch`` record a batch, and a
``slot.turn`` record an adopted hand-off: the admission from the slot's
release (:meth:`Replica.release`) over the batch that filled it to the
tick that took it up, on one clock.  Each lane's record says what of the
other lane was on the device's queue before its own dispatch (``behind``
/ ``behind_tick``).  A lane thread is always under a top-level ``mxt.*``
span — ``mxt.prefill.batch`` or ``mxt.prefill.wait``, ``mxt.decode.tick``
or ``mxt.decode.wait`` — but for the few lines of the gate.

Tracing (r12): when ``telemetry.tracing`` is on, each request carries
its span context across the lane threads (``req.trace``): the prefill
lane records the ``queue`` and ``prefill`` spans at admission, adoption
records ``handoff``, every decode tick records one ``decode.step`` span
per traced slot, and :meth:`Replica.finish` seals the trace (``evict``
event + the root span) — all retroactive from stamps the lanes already
take, so the decode tick pays one dict append per traced slot.  The
failure paths emit ``status="error"`` request records tagged with
replica + lane and trip the flight recorder (``tracing.incident``).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import NamedTuple

import numpy as np
from jax.profiler import TraceAnnotation

from .. import sanitizer as _san
from .. import telemetry
from ..telemetry import capacity
from ..telemetry import tracing
from .bucketing import pad_batch
from .kv_cache import PagedKVCacheManager
from .protocol import ServerClosedError
from .scheduler import RequestQueue

__all__ = ["PrefillLane", "DecodeLane", "Replica", "ReplicaDispatcher"]


def _lane_materialize(arrays):
    """The lanes' designated device→host sync point (first tokens at
    the prefill→decode handoff, token vectors at each decode tick) —
    the only def in this module sanctioned for eager syncs by
    tools/lint's ``MATERIALIZE_DEFS``, mirroring
    ``scheduler._materialize``."""
    out = []
    for a in arrays:
        if hasattr(a, "asnumpy"):
            out.append(a.asnumpy())
        else:
            out.append(np.asarray(a))
    return out


def _cache_layers(engine):
    """``kv_layers`` / ``state_layers`` of the engine's cache spec, for
    a lane's first record ({} for an engine that keeps none)."""
    spec = getattr(engine, "cache_spec", None)
    if spec is None:
        return {}
    out = {"kv_layers": spec.kv_layers, "state_layers": spec.state_layers,
           "cache_passes": spec.passes,
           "kv_bytes_per_token": getattr(engine, "kv_bytes_per_token", 0)}
    if getattr(engine, "num_blocks", None):
        out["pool_tokens"] = engine.num_blocks * engine.block_size
    if spec.latent_layers:
        out["latent_layers"] = spec.latent_layers
    if getattr(engine, "linear_attention", None):
        out["linear_attention"] = engine.linear_attention
    return out


def _scan_rows(engine, n_tokens, bucket_rows):
    """``scan_rows`` / ``scan_rows_padded`` of a ``prefill.batch``
    record: the true rows and the bucket's rows that went through a
    linear-attention layer's chunked scan ({} for a model without)."""
    if not getattr(engine, "linear_attention", None):
        return {}
    return {"scan_rows": int(n_tokens), "scan_rows_padded": int(bucket_rows)}


class _Handoff:
    """One admitted request crossing the prefill→decode boundary: its
    KV rows are already scattered into its blocks; the decode lane just
    adopts the slot."""

    __slots__ = ("req", "slot", "first", "batch", "freed", "t_handoff")

    def __init__(self, req, slot, first, batch=None, freed=None):
        self.req = req
        self.slot = slot
        self.first = first      # None: the prefill yielded no token
        self.batch = batch      # ``seq`` of the batch that prefilled it
        # the slot's last release, ``Replica.released[slot]`` as the
        # admission found it (None: the slot held nothing before)
        self.freed = freed
        self.t_handoff = None   # queued for the decode lane (hand_off)


class _Flight(NamedTuple):
    """A step the decode lane has queued and not yet booked: the
    engine's handle and what the turn that dispatched it knew."""

    step: object        #: the engine's ``StepHandle``
    t_loop: float       #: the top of the turn that queued it
    ids: tuple          #: the request of each of the step's slots
    ending: frozenset   #: the slots whose last token the step produces
    adopted: tuple      #: the hand-offs whose first step it is
    parked: int         #: the slots it left out for want of a block


class PrefillLane:
    """Admission + prompt forward + KV commit, one thread per replica."""

    def __init__(self, replica, poll_s=0.02):
        self.r = replica
        self.poll_s = float(poll_s)
        self._stop = threading.Event()
        self._drain = True
        self._thread = None
        self.error = None
        self._gate = None   # why the last _admit_batch ran nothing
        # busy / gated / idle seconds and the batch count (the ``seq``
        # of ``prefill.batch`` records), always on (tracing.lane_state)
        self.clock = tracing.LaneClock(replica.index)

    @property
    def gate(self):
        """What the FIFO head waited for when the lane last looked and
        ran nothing: ``"slot"``, ``"block"``, ``"tokens"``; None where
        it took a batch or found the queue empty.  Read by the decode
        lane (one attribute, no lock)."""
        return self._gate

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop,
                name=f"mxt-prefill-r{self.r.index}", daemon=True)
            self._thread.start()

    def request_stop(self, drain=True):
        self._drain = drain
        self._stop.set()

    def join(self):
        """Join the lane thread; a captured lane-machinery error is
        re-raised here — the lane's materialization point."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            raise self.error

    def alive(self):
        """Lane-thread liveness (the /healthz signal)."""
        return self._thread is not None and self._thread.is_alive()

    def _loop(self):
        # per-request failures are handled inside _admit_batch; this
        # catches lane-machinery bugs so the thread never dies silently
        try:
            self._run()
        except Exception as exc:
            self.error = exc
            tracing.incident("lane_thread_error",
                             context={"replica": self.r.index,
                                      "lane": "prefill",
                                      "error": repr(exc)})

    def _run(self):
        q = self.r.queue
        while True:
            if self._stop.is_set():
                if not self._drain or not len(q):
                    break
            if not self._admit_batch() and not self._stop.is_set():
                if self._gate is not None:
                    # queue non-empty but gated on capacity: wait for an
                    # eviction to free slots/blocks (wait_for_item would
                    # return immediately and busy-spin against decode)
                    self.clock.enter("gated", time.perf_counter(),
                                     self._gate)
                    with TraceAnnotation("mxt.prefill.wait",
                                         reason=self._gate,
                                         replica=self.r.index):
                        self.r.capacity_evt.wait(self.poll_s)
                    self.r.capacity_evt.clear()
                else:
                    self.clock.enter("idle", time.perf_counter())
                    with TraceAnnotation("mxt.prefill.wait", reason="empty",
                                         replica=self.r.index):
                        q.wait_for_item(self.poll_s)
        self.clock.enter("idle", time.perf_counter())

    def _bucket(self, req):
        """Prompt-length bucket — of the NOVEL SUFFIX when the radix
        prefix cache is on (the prefill program only sees the suffix;
        ``match_len`` is non-mutating, so bucketing probes don't churn
        LRU state).  The prefill thread is the trie's only mutator, so
        the probe here and the real lookup in ``_admit_batch`` agree."""
        plen = len(req.prompt_ids)
        if self.r.radix is not None:
            plen -= self.r.radix.match_len(req.prompt_ids)
        return self.r.policy.length_bucket(plen)

    def _admit_batch(self):
        """One prefill batch: gate → admit → forward (unlocked) →
        commit (locked) → handoff.  Returns True if anything ran;
        otherwise ``self._gate`` says what the FIFO head waits for
        (``"slot"``, ``"block"``, ``"tokens"``; None: nothing queued)."""
        r = self.r
        mgr = r.mgr
        self._gate = None
        queued = len(r.queue)
        if not queued:
            return False
        free_slots = mgr.free_slots()
        if not free_slots:
            self._gate = "slot"
            return False
        taken = []      # (prompt, max_new_tokens, shared blocks) each
        tokens = 0

        def accept(req):
            # the lane's own batch policy: greedy by token count while
            # the manager finds the state with the batch admitted safe,
            # not a fixed request count (a radix hit's shared prefix
            # takes no fresh block)
            nonlocal tokens
            shared = 0 if r.radix is None else \
                r.radix.match_len(req.prompt_ids) // mgr.block_size
            entry = (len(req.prompt_ids), req.max_new_tokens, shared)
            # a refusal of the FIFO head (nothing taken) gates the lane
            if len(taken) >= free_slots:
                self._gate = "slot"
                return False
            if not mgr.admissible(taken + [entry]):
                self._gate = "block"
                telemetry.count("serving.kv.unsafe_refusals|at=admit")
                return False
            if tokens and tokens + entry[0] > r.max_prefill_tokens:
                self._gate = "tokens"
                return False
            taken.append(entry)
            tokens += entry[0]
            return True

        with contextlib.ExitStack() as gate:
            # held from the gate to the admits (_prefill_group lets go):
            # growth asks for the same lock, so no grant lands between
            # what the gate found safe and the admission
            gate.enter_context(mgr.admission())
            group = r.queue.take_batch(
                self._bucket, min(free_slots, r.policy.max_batch), accept)
            if not group:
                return False
            self._gate = None   # a refusal after the head only ends the batch
            with TraceAnnotation("mxt.prefill.batch",
                                 seq=self.clock.batches + 1,
                                 replica=r.index):
                self._prefill_group(group, free_slots, queued, gate)
        return True

    def _forward(self, group, prompts, matched, skip, block_lists,
                 t0s_suf, s0s, kb):
        """Dispatch the prompt forward (unlocked); returns the device
        first-token vector, the raw K/V rows for the commit and which
        attention the program ran (``"flash"`` or ``"dense"``: the
        engine's rule at this bucket; the suffix path is dense)."""
        r = self.r
        eng = r.engine
        if r.radix is None or not any(matched):
            toks, rows = eng.prefill_rows(prompts, t0s_suf)
            at = getattr(eng, "prefill_attention_at", None)
            return toks, rows, at(prompts.shape[1]) if at else "dense"
        # radix-hit path: dense prefix copies (locked gather) feed the
        # suffix-only forward (unlocked); the commit scatters ONLY the
        # suffix rows into the request's private blocks past the shared
        # prefix
        pre_lb = r.policy.length_bucket(max(matched))
        nbp_pre = -(-pre_lb // r.mgr.block_size)
        rows_idx = np.full((kb, nbp_pre), eng.num_blocks, np.int32)
        for i in range(len(group)):
            rows_idx[i, :skip[i]] = block_lists[i][:skip[i]]
        pre_kv = eng.gather_prefix(rows_idx)
        toks, rows = eng.prefill_suffix(pre_kv, prompts, t0s_suf, s0s)
        return toks, rows, "dense"

    def _prefill_group(self, group, free_slots, queued, gate):
        """The ``group`` the gate took through admission, forward,
        commit and handoff, stamped once at each boundary for the lane
        log, the capacity duty cycle and the requests' span trees alike.
        ``free_slots`` and ``queued``: the counts at the gate that took
        the group (the group's own requests among the queued), for its
        record.  ``gate``: holds the manager's lock since the gate
        looked; closed here once the group is admitted."""
        r = self.r
        mgr = r.mgr
        t_start, c_start = tracing.clocks()
        self.clock.enter("busy", t_start)
        seq = self.clock.batches
        lb = self._bucket(group[0])
        kb = r.policy.batch_bucket(len(group))
        eng = r.engine
        rx = r.radix
        try:
            if rx is not None:
                # real lookup (bumps LRU, counts hits); no references
                # are taken until admit() shares under the manager lock
                t_rx0 = time.perf_counter()
                matched, shared = [], []
                for req in group:
                    m, blks = rx.lookup(req.prompt_ids)
                    matched.append(m)
                    shared.append(blks)
                t_rx1 = time.perf_counter()
                hits = sum(1 for m in matched if m)
                telemetry.count("serving.radix_hits", hits)
                telemetry.count("serving.radix_misses",
                                len(group) - hits)
                if any(matched):
                    telemetry.count("serving.radix_hit_tokens",
                                    sum(matched))
            else:
                t_rx0 = t_rx1 = t_start
                matched = [0] * len(group)
                shared = [None] * len(group)
            prompts = pad_batch(
                [np.asarray(q.prompt_ids[matched[i]:], np.int32)
                 for i, q in enumerate(group)], kb, lb)
            t0s = np.full(kb, len(group[0].prompt_ids), np.int32)
            t0s_suf = np.full(
                kb, len(group[0].prompt_ids) - matched[0], np.int32)
            s0s = np.zeros(kb, np.int32)
            skip = np.zeros(kb, np.int32)
            slots = np.full(kb, eng.num_slots, np.int32)
            block_lists = [None] * kb
            freed = [None] * kb
            for i, req in enumerate(group):
                t0s[i] = len(req.prompt_ids)
                t0s_suf[i] = t0s[i] - matched[i]
                s0s[i] = matched[i]
                skip[i] = matched[i] // mgr.block_size
                slot, blocks = mgr.admit(req.id, int(t0s[i]),
                                         req.max_new_tokens,
                                         step=eng.steps,
                                         shared_blocks=shared[i] or None)
                slots[i] = slot
                block_lists[i] = blocks
                freed[i] = r.released.get(int(slot))
                req.slot = int(slot)
                # its claim on the pool; it holds the prompt's now
                req.kv_blocks = mgr.blocks_for(int(t0s[i]),
                                               req.max_new_tokens)
                if rx is not None:
                    req.prefix_hit_tokens = matched[i]
                req.replica = r.index
                req.joined_step = eng.steps
                req.t_start = t_start
                req.bucket = (kb, lb)
                req.batch_size = len(group)
            gate.close()
            with telemetry.span("serving.prefill",
                                {"lane": "prefill", "replica": r.index,
                                 "batch": kb, "length": lb}):
                with TraceAnnotation("mxt.prefill.dispatch", seq=seq,
                                     replica=r.index):
                    toks, rows, attention = self._forward(
                        group, prompts, matched, skip, block_lists,
                        t0s_suf, s0s, kb)
                # the forward is on the device's queue: say so to the
                # decode lane, whose next step runs behind it, and note
                # the step that was queued first, which it runs behind
                eng.prefill_in_flight = (seq,)
                behind_tick = eng.step_in_flight
                t_disp1, c_disp1 = tracing.clocks()
                with TraceAnnotation("mxt.prefill.fetch", seq=seq,
                                     replica=r.index):
                    first = _lane_materialize([toks])[0]
                eng.prefill_in_flight = ()
                t_ready, c_ready = tracing.clocks()
                # a model with routed experts sends their row counts
                # behind the first tokens, in the same fetch
                first, extra = eng.split_fetch(first, kb) \
                    if hasattr(eng, "split_fetch") else (first, {})
                with TraceAnnotation("mxt.prefill.commit", seq=seq,
                                     replica=r.index):
                    t_lock, t_commit1 = eng.commit_rows(
                        rows, slots, block_lists, t0s, first,
                        skip_blocks=skip)
            if rx is not None:
                # register the full prompt blocks (device-ordered after
                # the commit scatter) so later requests share them
                for i, req in enumerate(group):
                    rx.insert(req.prompt_ids, block_lists[i])
            if r.draft is not None:
                # the draft prefills the FULL prompt into its slots' own
                # blocks; the commit aligns its mirror with the target's
                # first token (the draft's own is fetched by nobody)
                lbf = r.policy.length_bucket(
                    max(len(q.prompt_ids) for q in group))
                fulls = pad_batch([np.asarray(q.prompt_ids, np.int32)
                                   for q in group], kb, lbf)
                _, rows = r.draft.prefill_rows(fulls, t0s)
                r.draft.commit_rows(
                    rows, slots,
                    [None if b is None else r.draft_blocks(int(s))
                     for s, b in zip(slots, block_lists)], t0s, first)
        except Exception as exc:
            gate.close()
            eng.prefill_in_flight = ()
            self.clock.enter("idle", time.perf_counter())
            for req in group:
                if req.slot is not None and req.slot in mgr._active:
                    r.release(req)
                req.replica = r.index
                req.future.set_exception(exc)
                r.fail(req, exc, lane="prefill")
            r.capacity_evt.set()
            tracing.incident("replica_exception",
                             context={"replica": r.index,
                                      "lane": "prefill",
                                      "error": repr(exc)})
            return
        t_first, c_first = tracing.clocks()
        self.clock.enter("idle", t_first)
        mates = [req.id for req in group]
        # a block decoder's prefill stores the prompt's whole blocks and
        # yields no token: its requests' first comes from the decode lane
        yields = getattr(eng, "block", None) is None
        if seq == 1:
            extra.update(_cache_layers(eng))
        # a bucket decides its routed experts' product for itself, as
        # it does its attention (None: a model without experts)
        product = getattr(eng, "expert_product_at", None)
        # one stamp set for every consumer: the lane log, the capacity
        # duty cycle and (below) the request's span tree; the host
        # part's four ends on this thread's CPU clock too (c_*)
        tracing.lane_record(
            "prefill.batch", replica=r.index, seq=seq,
            request_ids=tuple(mates),
            n_tokens=int(t0s_suf[:len(group)].sum()), bucket=(kb, lb),
            radix_hit_tokens=int(sum(matched)), t_start=t_start,
            t_disp1=t_disp1, t_ready=t_ready, t_lock=t_lock,
            t_commit1=t_commit1, t_first=t_first, c_start=c_start,
            c_disp1=c_disp1, c_ready=c_ready, c_first=c_first,
            prefill_attention=attention, behind_tick=behind_tick,
            free_slots=free_slots, queued=queued,
            passes=getattr(getattr(eng, "cache_spec", None), "passes", 1),
            kv_bytes=int(t0s_suf[:len(group)].sum())
            * getattr(eng, "kv_bytes_per_token", 0),
            expert_product=product(kb * lb) if product else None,
            **(eng.selection_counts(t0s_suf[:len(group)], whole=True)
               if hasattr(eng, "selection_counts") else {}),
            **_scan_rows(eng, t0s_suf[:len(group)].sum(), kb * lb), **extra)
        capacity.lane_busy(r.index, "prefill", t_start, t_first)
        for i, req in enumerate(group):
            req.t_commit = t_first
            if yields:
                req.t_first = t_first
            if rx is not None and matched[i] and t0s_suf[i] > 0:
                # prefill cost scales ~linearly in prompt tokens, so
                # the saved share is the reused fraction scaled onto
                # the measured suffix prefill (a documented estimate)
                pf_ms = (t_first - t_rx1) * 1e3
                req.prefill_saved_ms = pf_ms * matched[i] \
                    / int(t0s_suf[i])
            if req.trace is not None:
                # retroactive spans from the stamps above: queue covers
                # dispatch + bucket dwell, prefill the forward + commit
                req.trace.add("queue", req.t_submit, t_start,
                              replica=r.index)
                if rx is not None:
                    req.trace.add("radix_lookup", t_rx0, t_rx1,
                                  replica=r.index,
                                  hit_tokens=matched[i])
                req.trace.add("prefill", t_start, t_first,
                              replica=r.index, slot=req.slot,
                              kv_blocks=req.kv_blocks,
                              bucket=list(req.bucket),
                              mates=[m for m in mates if m != req.id])
            if not yields:
                r.decode.hand_off(_Handoff(req, req.slot, None, seq,
                                           freed[i]))
            elif mgr.consume(req.slot):
                # max_new_tokens == 1: done at prefill, never decodes
                r.finish(req, [int(first[i])])
            else:
                r.decode.hand_off(_Handoff(req, req.slot, int(first[i]),
                                           seq, freed[i]))
        telemetry.count("serving.admitted", len(group))


class DecodeLane:
    """Slot-set advancement, one thread per replica: adopt handoffs,
    tick every in-flight slot, evict finished requests (returning their
    KV blocks to the pool)."""

    def __init__(self, replica, poll_s=0.005):
        self.r = replica
        self.poll_s = float(poll_s)
        self._handoffs = deque()
        self._hand_lock = _san.wrap_lock(
            threading.Lock(), "lanes.DecodeLane._hand_lock")
        self._seqs = {}       # slot -> (request, [generated tokens])
        self._wake = threading.Event()   # set on hand_off: adopt now
        self._stop = threading.Event()
        self._thread = None
        self.error = None
        # the turn's first stamp (on the wall's clock and this thread's)
        # and the hand-offs it adopted, set by _adopt for the tick's
        # lane-log record and its turns'
        self._t_loop = self._c_loop = None
        self._adopted = ()
        # seconds the lane has waited with nothing to step since its
        # last record, and the CPU seconds its polling took (the next
        # record's ``idle_s`` and ``idle_cpu_s``); when the wait under
        # way began, on both clocks
        self._idle_s = self._idle_cpu_s = 0.0
        self._idle_from = None
        # the token-at-a-time tick runs a step ahead of its bookkeeping:
        # the step it has queued and not yet booked, and when the last
        # booked step's tokens came (a step has the device from then)
        self._flight = None
        self._t_tok = 0.0
        # the hand-offs it has adopted that no step has carried yet (a
        # turn may queue nothing): the first step's ``slot.turn`` records
        self._unstepped = ()
        # the engine's attention path goes into its first tick record
        self._said_attention = False

    def hand_off(self, h):
        h.t_handoff = time.perf_counter()
        with self._hand_lock:
            self._handoffs.append(h)
        self._wake.set()

    def pending(self):
        with self._hand_lock:
            return len(self._handoffs) + len(self._seqs)

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop,
                name=f"mxt-decode-r{self.r.index}", daemon=True)
            self._thread.start()

    def request_stop(self):
        self._stop.set()

    def join(self):
        """Join the lane thread; a captured lane-machinery error is
        re-raised here — the lane's materialization point."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            raise self.error

    def alive(self):
        """Lane-thread liveness (the /healthz signal)."""
        return self._thread is not None and self._thread.is_alive()

    def snapshot(self):
        """In-flight view for the /requests table: handoffs not yet
        adopted + decoding slots, host-side bookkeeping only."""
        rows = []
        with self._hand_lock:
            handoffs = list(self._handoffs)
            seqs = dict(self._seqs)
        for h in handoffs:
            rows.append({"request_id": h.req.id, "state": "handoff",
                         "slot": h.slot, "replica": self.r.index})
        for slot, (req, tokens) in seqs.items():
            rows.append({"request_id": req.id, "state": "decoding",
                         "slot": slot, "replica": self.r.index,
                         "tokens_done": len(tokens),
                         "max_new_tokens": req.max_new_tokens})
        return rows

    def _loop(self):
        # per-request failures are handled inside _tick; this catches
        # lane-machinery bugs so the thread never dies silently
        try:
            self._run()
        except Exception as exc:
            self.error = exc
            tracing.incident("lane_thread_error",
                             context={"replica": self.r.index,
                                      "lane": "decode",
                                      "error": repr(exc)})

    def _run(self):
        spec = self.r.spec_k > 0 and self.r.draft is not None
        tick = self._tick_spec if spec else self._tick
        while True:
            if self.pending() or self._flight is not None:
                # one turn: adopt, then advance every slot one tick (a
                # slot stays in _seqs until its last step is booked, so
                # a step queued and not yet booked keeps the lane turning;
                # a block pass may carry none but slots whose requests
                # the pass before it ended, and is fetched all the same)
                # ``seq``: the step the turn queues; ``books``: the one
                # whose tokens it fetches and books (the same step, but
                # for the tick that runs ahead: the step in flight, 0
                # where none is)
                seq = books = self.r.engine.steps + 1
                if not spec:
                    books = self._flight.step.seq if self._flight else 0
                with TraceAnnotation("mxt.decode.tick", seq=seq,
                                     replica=self.r.index, books=books):
                    self._adopt()
                    tick()
            elif self._stop.is_set():
                break
            else:
                self._rest()

    def _adopt(self):
        """Pull every pending handoff into this lane's slot set.  The
        KV rows are already in the request's blocks (the prefill lane
        committed them before handing off), so adoption is pure
        bookkeeping — decode only ever advances slots it has adopted,
        never a slot whose commit is still in flight.  Stamps the top
        of the lane's turn (``t_loop``) and keeps the hand-offs for the
        tick's record and their ``slot.turn`` records, which the tick
        writes once its ``t_tok`` is known."""
        self._t_loop, self._c_loop = tracing.clocks()
        if self._idle_from is not None:
            # the wait ends where a turn begins: one stretch, however
            # many polls it took
            t0, c0 = self._idle_from
            self._idle_from = None
            self._idle_s += self._t_loop - t0
            self._idle_cpu_s += self._c_loop - c0
        with self._hand_lock:
            taken = tuple(self._handoffs)
            self._handoffs.clear()
            for h in taken:
                # a block decoder (no first token): output offset ->
                # token, filled in any order
                self._seqs[h.slot] = (h.req,
                                      {} if h.first is None else [h.first])
        self._adopted = taken
        if not taken:
            return
        # a turn crosses threads, so the xplane is linked by numbers:
        # this tick's seq and the batches whose requests it takes up
        with TraceAnnotation(
                "mxt.decode.adopt", seq=self.r.engine.steps + 1,
                replica=self.r.index,
                batch=" ".join(str(b) for b in sorted(
                    {h.batch for h in taken if h.batch is not None}))):
            for h in taken:
                h.req.t_handoff = time.perf_counter()
                hand_ms = (h.req.t_handoff - h.req.t_commit) * 1e3
                telemetry.hist("serving.handoff_ms", hand_ms)
                telemetry.hist(
                    f"serving.handoff_ms|replica={self.r.index}", hand_ms)
                if h.req.trace is not None:
                    h.req.trace.add("handoff", h.req.t_commit,
                                    h.req.t_handoff, replica=self.r.index,
                                    slot=h.slot)
                if h.first is None:
                    # the request's log of every commit
                    h.req.commits = []

    def _abort(self, exc):
        """An engine call of a turn raised: fail the request of every
        slot the lane holds (the stepped and the parked alike) and free
        the slot."""
        r = self.r
        with self._hand_lock:
            held = sorted(self._seqs)
        for slot in held:
            with self._hand_lock:
                req, _ = self._seqs.pop(slot)
            r.release(req)
            req.future.set_exception(exc)
            r.fail(req, exc, lane="decode")
        r.capacity_evt.set()
        tracing.incident("replica_exception",
                         context={"replica": r.index, "lane": "decode",
                                  "error": repr(exc)})

    def _grant(self, active, n=1):
        """Before a step is queued: the manager grants each of
        ``active`` the blocks its next ``n`` writes land in
        (``grant_step``: oldest admission first, under the safe-state
        rule) and the engine's tables take them -> (the slots the step
        takes, how many it leaves out).  A slot refused is PARKED for
        this step: left out of ``active``, it runs as a vacant row (the
        token tick; a block pass or a verify computes its row and books
        nothing of it, and what it would write past its blocks drops at
        the sentinel), its cursor, its count and its blocks as they
        were, and is asked about again before the next step; nothing is
        evicted and no token differs."""
        r = self.r
        if not active:
            return active, 0
        grants, parked = r.mgr.grant_step(active, n)
        for slot, (at, blocks) in grants.items():
            r.engine.set_blocks(slot, at, blocks)
        if grants:
            telemetry.count("serving.kv.grants",
                            sum(len(b) for _, b in grants.values()))
        if parked:
            telemetry.count("serving.kv.parked_slot_ticks", len(parked))
            telemetry.count("serving.kv.unsafe_refusals|at=grant",
                            len(parked))
            active = [s for s in active if s not in parked]
        return active, len(parked)

    def _rest(self):
        """Wait for a hand-off: an empty lane, or a turn that found
        every slot parked and nothing to fetch, which only a hand-off
        (or a failed prefill's release) changes.  The wait is no part
        of a turn's host time: the next record says how long it was,
        from here to the top of the next turn (:meth:`_adopt`)."""
        if self._idle_from is None:
            self._idle_from = tracing.clocks()
        with TraceAnnotation("mxt.decode.wait", replica=self.r.index):
            self._wake.wait(self.poll_s)
        self._wake.clear()

    def _serial_step(self, n):
        """What a tick that books its own step (the speculative tick)
        steps: every slot the lane holds that is
        granted the blocks of its next ``n`` writes -> (slots, their
        requests' ids, slots parked, the hand-offs whose first step
        this is), or None where every slot is parked: the turn has
        rested, and its hand-offs' records wait for the next step."""
        with self._hand_lock:
            held = sorted(self._seqs)
        adopted, self._unstepped = self._unstepped + self._adopted, ()
        active, n_parked = self._grant(held, n)
        if not active:
            self._unstepped = adopted
            self._rest()
            return None
        with self._hand_lock:
            ids = tuple(self._seqs[s][0].id for s in active)
        return active, ids, n_parked, adopted

    def _note_tick(self, active, t_busy0, t_tok):
        """What every kind of tick counts once its engine call is back."""
        r = self.r
        r.batches += 1
        telemetry.hist("serving.batch_size", len(active))
        telemetry.gauge("serving.kv_blocks_in_use",
                        r.mgr.allocator.blocks_in_use)
        # retroactive capacity accounting from the engine's stamps: the
        # busy interval, batch occupancy, and pool pressure per tick.
        # Gated on is_enabled() so the argument expressions impose no
        # attribute contract (or cost) on duck-typed engines/managers
        # when capacity accounting is off.
        if capacity.is_enabled():
            capacity.note_tick(r.index, len(active),
                               getattr(r.engine, "num_slots", len(active)),
                               t_busy0, t_tok)
            capacity.note_kv(r.index, r.mgr.allocator.free_blocks,
                             r.mgr.num_blocks)

    def _prefill_covers(self, step):
        """Whether the device will have a prefill's forward to run when
        ``step``, the one in flight, ends: one is queued behind it, or
        the prefill lane is about to queue one (a request waits, a slot
        is free and the lane is not gated on blocks: it dispatches
        within a millisecond or two).
        The host's turn then hides behind that forward as it would
        behind a step queued ahead, and a step queued now would only
        stand between this admission's forward and the next one's: the
        prefill lane fetches each forward's first token before it queues
        the next, so with two steps always on the queue it admits one
        request every two steps, and a cell that admits more stands with
        slots empty (``lfm2_24b.chat_decode_sat``, 0.8 a step: 94 -> 60%
        of the slots held, ``PERF.md`` section 6, PR 39).  A guess either
        way costs time, never a token: wrongly true, the device rests
        one turn of the host's, as it did every tick before the lane ran
        ahead; wrongly false, that forward runs a step later."""
        r = self.r
        if any(b not in step.behind for b in r.engine.prefill_in_flight):
            return True
        # a lane that its last look at the queue left gated on BLOCKS
        # queues nothing while slots stand free: it waits for a request
        # to end, which is this lane's to book, and the device would
        # rest a turn of the host's every step (a pool smaller than
        # slots x max_length: ``PERF.md`` section 6, PR 40)
        return len(r.queue) > 0 and r.mgr.free_slots() > 0 \
            and r.prefill.gate != "block"

    def _tick(self):
        """A turn of the lane, one step ahead of its bookkeeping: queue
        step K+1, then fetch and book step K, so a step is on the
        device's queue while the host works.  Step K+1 needs nothing of
        step K's that the host does not know already: its tokens pass
        from step to step on the device (``engine.dispatch_step``), the
        block a write lands in is granted from the cursor the host
        already has (:meth:`_grant`: a slot refused is parked, left out
        of step K+1 and tried again for K+2, its token then the host's),
        a cursor moves by one and a request ends by count.  So the
        manager's count is taken as a step is QUEUED: a slot whose last
        token step K produces is left out of step K+1 (it runs vacant
        there) and its request is finished, and the slot released, when
        step K's tokens are booked.

        A block decoder's pass (``engine.block``) is queued the same
        way, its blocks' ids, masks, pass counts and cursors carried on
        the device.  A block's progress depends on data, so two things
        differ.  The cursor may move a block on in the pass not yet
        fetched: a slot is granted the blocks of two blocks' writes
        behind the manager's cursor, a pass late.  And a request ends
        with the pass that commits the last of its positions, which
        nobody knows as pass K+1 is queued: that pass may carry a slot
        that pass K finished, a row computed and booked for nobody
        (``BlockTick.live``), the slot released as pass K is booked.

        A hand-off adopted in a turn rides the step that turn queues, or
        the next one queued.  A lane's first turn, and the first
        after it stood empty, books nothing; the turn that finds nothing
        more to step queues nothing, and so does a turn that finds the
        prefill lane about to use the device when step K ends
        (:meth:`_prefill_covers`): the turn after it queues step K+1
        behind that forward."""
        r = self.r
        eng = r.engine
        block = getattr(eng, "block", None)
        prev, self._flight = self._flight, None
        with self._hand_lock:
            active = [s for s in sorted(self._seqs)
                      if prev is None or s not in prev.ending]
        if prev is not None and self._prefill_covers(prev.step):
            active = ()
        active, n_parked = self._grant(
            active, 1 if block is None else 2 * block.block_len)
        with self._hand_lock:
            ids = tuple(self._seqs[s][0].id for s in active)
        adopted, self._unstepped = self._unstepped + self._adopted, ()
        # the turn's own dispatch, for its record: an instant where it
        # queues nothing
        t_lock = t_disp0 = None
        try:
            if active:
                step = eng.dispatch_step(active)
                ending = set()
                if block is None:
                    for slot in active:
                        r.mgr.advance(slot)   # the step writes K/V at its pos
                        if r.mgr.consume(slot):
                            ending.add(slot)
                self._flight = _Flight(step, self._t_loop, ids,
                                       frozenset(ending), adopted,
                                       n_parked)
                t_lock, t_disp0 = step.t_lock, step.t_disp0
            else:
                self._unstepped = adopted
            # the count is the host's work, not a wait for tokens: the
            # turn's ``t_disp1`` is where the lane turns to the fetch
            t_disp1, c_disp1 = tracing.clocks()
            if not active:
                t_lock = t_disp0 = t_disp1
            if prev is None:
                if n_parked and not active:
                    self._rest()
                return
            out = eng.fetch_step(prev.step)
        except Exception as exc:
            # both steps' requests are in _seqs, each once
            self._flight, self._unstepped = None, ()
            eng.drop_steps()
            self._abort(exc)
            return
        step = prev.step
        # the device was the step's from its dispatch or, run ahead, from
        # the tokens of the step before it
        t_busy0, self._t_tok = max(step.t_disp0, self._t_tok), step.t_tok
        book = self._book_tokens if block is None else self._book_blocks
        ids, n_finished, extra = book(prev, out, t_busy0)
        self._record_tick(step, ids, n_finished, prev.adopted,
                          queued_at=prev.t_loop,
                          turn=dict(t_lock=t_lock, t_disp0=t_disp0,
                                    t_disp1=t_disp1, c_disp1=c_disp1),
                          n_parked=prev.parked, **extra,
                          **step.experts, **step.selection)

    def _book_tokens(self, flight, toks, t_busy0):
        """Step ``flight``'s tokens, one a slot, to their requests ->
        (the record's ``request_ids``, requests finished, no further
        field)."""
        r = self.r
        step = flight.step
        self._note_tick(step.active, t_busy0, step.t_tok)
        n_finished = 0
        with TraceAnnotation("mxt.decode.book", seq=step.seq,
                             replica=r.index):
            for slot in map(int, step.active):
                with self._hand_lock:
                    req, tokens = self._seqs[slot]
                tokens.append(int(toks[slot]))
                if req.first_tick is None:
                    req.first_tick = step.seq
                if req.trace is not None:
                    # one span per traced slot per tick: the per-request
                    # decode slice (cost: one dict append — the tracing
                    # A/B lane in benchmark/serving_latency.py bounds it)
                    req.trace.add("decode.step", t_busy0, step.t_tok,
                                  step=step.seq, batch=len(step.active),
                                  replica=r.index, slot=slot)
                if slot in flight.ending:
                    with self._hand_lock:
                        del self._seqs[slot]
                    if step.selected is not None:
                        # the step's query stood one before the cursor
                        req.selected = (int(step.pos[slot]) - 1,
                                        r.engine.selection_of(slot, step))
                    r.finish(req, tokens, step=step.seq)
                    n_finished += 1
        return flight.ids, n_finished, {}

    def _book_blocks(self, flight, tick, t_busy0):
        """A block pass's ``generative.BlockTick`` to the requests of
        its live slots -> (their ids, requests finished, the record's
        block fields, which count the live rows alone).  A slot commits
        0 to a block's length of tokens, in any order of position; its
        cursor, and the manager's, move only when the pass was the one
        over its finished block.  A request's output is the tokens at
        its first ``max_new_tokens`` positions behind the prompt: it
        ends with the pass that commits the last of them, wherever in a
        block that is.  ``req.commits`` keeps every commit ``(position,
        token, the block's pass)``, those past the output's end too:
        what each pass saw can be rebuilt from it."""
        r = self.r
        step = flight.step
        bl = r.engine.block.block_len
        live = [(int(s), rid) for s, rid in zip(step.active, flight.ids)
                if tick.live[s]]
        self._note_tick(live, t_busy0, step.t_tok)
        n_finished = 0
        with TraceAnnotation("mxt.decode.book", seq=step.seq,
                             replica=r.index):
            for slot, _rid in live:
                with self._hand_lock:
                    req, tokens = self._seqs[slot]
                if req.first_tick is None:
                    req.first_tick = step.seq
                if req.trace is not None:
                    req.trace.add("decode.step", t_busy0, step.t_tok,
                                  step=step.seq, batch=len(live),
                                  replica=r.index, slot=slot)
                if tick.stored[slot]:
                    # the block's K/V stays: the cursor is past it
                    st = r.mgr.state(slot)
                    r.mgr.advance_n(slot, min(int(tick.pos0[slot]) + bl,
                                              int(st.reserved))
                                    - int(st.pos))
                    continue
                done = False
                first = int(tick.pos0[slot]) - len(req.prompt_ids)
                for j in np.flatnonzero(tick.commit[slot]):
                    tok = int(tick.ids[slot, j])
                    req.commits.append((int(tick.pos0[slot]) + int(j), tok,
                                        int(tick.step[slot])))
                    if first + j < req.max_new_tokens:
                        tokens[first + int(j)] = tok
                        if req.t_first is None:
                            req.t_first = step.t_tok
                        done = r.mgr.consume(slot) or done
                if done:
                    with self._hand_lock:
                        del self._seqs[slot]
                    r.finish(req, [tokens[i]
                                   for i in range(req.max_new_tokens)],
                             step=step.seq)
                    n_finished += 1
        return tuple(rid for _s, rid in live), n_finished, dict(
            block_len=bl, rows=len(live) * bl,
            n_store=int(tick.stored.sum()),
            committed=int(tick.commit.sum()),
            block_passes=int((tick.step[tick.stored] + 1).sum()))

    def _record_tick(self, step, ids, n_finished, adopted, queued_at=None,
                     turn=None, **extra):
        """The ``decode.tick`` record of ``step`` (the engine's
        ``StepHandle``), its bookkeeping done, and a ``slot.turn`` record
        for each hand-off that it was the first step of (``adopted``).
        ``seq``, ``request_ids``, ``n_active`` (the slots it stepped),
        ``n_parked`` (passed in ``extra``: the slots held and left out
        of it for want of a block), ``n_adopted``,
        ``behind``, ``ahead``, ``kv_tokens`` (K/V rows the step attended,
        summed over its slots) and ``t_tok`` are the step's.  ``t_loop,
        t_lock, t_disp0, t_disp1`` are the stamps of the turn that
        fetched and booked it, in the order the lane thread passed them:
        a tick that runs ahead passes its dispatch's (of the NEXT step)
        as ``turn`` and the top of the turn that queued the step
        as ``queued_at``; for a serial tick they are the step's own,
        which every record also carries as ``t_step_loop``,
        ``t_step_lock``, ``t_step_disp0``, ``t_step_disp1``.  The
        turn's top and the two ends of its wait for the tokens are
        taken on the lane thread's CPU clock too (``c_loop``,
        ``c_disp1``, ``c_tok``: ``tracing.clocks``); ``idle_s`` is how
        long the lane waited with nothing to step since its last record
        and ``idle_cpu_s`` the CPU seconds its polling took.  The lane's
        first record also says which attention the engine's step program
        was built with, how many KV heads a stored pool row holds
        (``kv_pack``), which product its routed experts run
        (``expert_product``), how many layers keep K/V and how many a
        per-slot state, and which form its linear-attention layers take
        (``linear_attention``).  A model with per-slot state says in
        every record how many bytes of it the step read and wrote
        (``state_bytes``)."""
        r = self.r
        extra.update(
            t_step_loop=self._t_loop if queued_at is None else queued_at,
            t_step_lock=step.t_lock, t_step_disp0=step.t_disp0,
            t_step_disp1=step.t_disp1)
        if turn is None:
            turn = {k: getattr(step, k) for k in (
                "t_lock", "t_disp0", "t_disp1", "c_disp1")}
        idle_s, self._idle_s = self._idle_s, 0.0
        idle_cpu_s, self._idle_cpu_s = self._idle_cpu_s, 0.0
        extra.setdefault("kv_tokens", int(step.kv_tokens))
        # the K/V rows' bytes by the engine's spec, every pass counted,
        # and how much of the pool its requests hold (blocks granted)
        spec = getattr(r.engine, "cache_spec", None)
        passes = getattr(spec, "passes", 1)
        extra.update(
            passes=passes,
            kv_bytes=extra["kv_tokens"]
            * getattr(r.engine, "kv_bytes_per_token", 0),
            pool_reserved_tokens=r.mgr.allocator.blocks_in_use
            * r.mgr.block_size)
        per_slot = getattr(r.engine, "state_bytes_per_step", 0)
        if per_slot:
            # what the active slots' state layers read and wrote
            extra["state_bytes"] = per_slot * len(ids)
        if not self._said_attention:
            self._said_attention = True
            extra["decode_attention"] = getattr(
                r.engine, "decode_attention", None)
            extra["kv_pack"] = getattr(r.engine, "kv_pack", None)
            extra["expert_product"] = getattr(
                r.engine, "expert_product", None)
            extra["decoding"] = getattr(r.engine, "decoding", None)
            block = getattr(r.engine, "block", None)
            if block is not None:
                extra["block_decoding"] = block._asdict()
            extra.update(_cache_layers(r.engine))
        r.steps_ahead += step.ahead
        telemetry.count("serving.decode.steps")
        if spec is not None:
            telemetry.count("serving.decode.layer_applications",
                            passes * len(spec.layers))
        if step.ahead:
            telemetry.count("serving.decode.steps_ahead")
        tracing.lane_record(
            "decode.tick", replica=r.index, seq=step.seq,
            n_active=len(ids), n_adopted=len(adopted),
            n_finished=n_finished, request_ids=ids,
            behind=step.behind, ahead=step.ahead, idle_s=idle_s,
            idle_cpu_s=idle_cpu_s,
            t_loop=self._t_loop, c_loop=self._c_loop, **turn,
            t_tok=step.t_tok, c_tok=step.c_tok,
            t_book=time.perf_counter(), **extra)
        for h in adopted:
            # every stamp is one a boundary already took: the release's
            # ``t_done``, the batch's, the hand-off's, this step's
            t_free, prev_id, freed_by = h.freed or (None, None, None)
            tracing.lane_record(
                "slot.turn", replica=r.index, slot=h.slot,
                request_id=h.req.id, batch=h.batch, tick=step.seq,
                freed_by=freed_by, prev_request_id=prev_id, t_free=t_free,
                t_start=h.req.t_start, t_first=h.req.t_commit,
                t_handoff=h.t_handoff, t_adopt=h.req.t_handoff,
                t_tok=step.t_tok)

    def _tick_spec(self):
        """Speculative tick: k sequential DRAFT steps propose a window,
        ONE target verify scores all k+1 positions, and greedy
        token-exact acceptance commits the matched prefix plus (below
        full acceptance) the target's correction token — bit-identical
        output to plain decode (every emitted token is a target argmax
        given previously emitted tokens), at one target forward per
        up-to-k tokens.

        Rollback is host-side only: the manager's cursor advances by
        the full window then truncates to the accepted position; the
        rejected rows' K/V sits masked in the pool until the next
        window overwrites it (kv_cache.truncate's stale-row
        contract)."""
        r = self.r
        k = r.spec_k
        # the verify writes the window's k + 1 rows
        taken = self._serial_step(k + 1)
        if taken is None:
            return
        active, ids, n_parked, adopted = taken
        t0 = time.perf_counter()
        proposals = np.zeros((r.engine.num_slots, k), np.int32)
        try:
            for j in range(k):
                # draft mirrors auto-advance, so step j+1 is
                # conditioned on the draft's own proposal j
                proposals[:, j] = r.draft.step(active)
            pos0 = r.engine.positions()
            out = r.engine.verify(proposals)
        except Exception as exc:
            self._abort(exc)
            return
        # the verify's stamps; the k draft steps lie in [t0, t_lock]
        step = r.engine.booked
        t_lock, t_tok = step.t_lock, step.t_tok
        self._note_tick(active, t0, t_tok)
        accepted_this_tick = 0
        accepted = {}       # request id -> tokens this tick committed
        n_finished = 0
        step_idx = step.seq
        with TraceAnnotation("mxt.decode.book", seq=step_idx,
                             replica=r.index):
            for slot in active:
                d, g = proposals[slot], out[slot]
                m = 0
                while m < k and d[m] == g[m]:
                    m += 1
                st = r.mgr.state(slot)
                # accepted = matched drafts + the target's own next token,
                # capped at k (on full acceptance the bonus token is NOT
                # taken: the draft's cache only holds rows for [last,
                # d1..d_{k-1}], so emitting g_{k+1} would leave the draft a
                # KV row short and poison every later proposal) and clamped
                # to the tokens still owed (never over-emit)
                acc = min(m + 1, k, int(st.remaining))
                adv = min(k + 1, int(st.reserved) - int(st.pos))
                r.mgr.advance_n(slot, adv)
                if r.mgr.truncate(slot, int(pos0[slot]) + acc):
                    # blocks that held rejected rows only went back
                    r.engine.set_blocks(slot, len(st.blocks))
                last = int(g[acc - 1])
                r.engine.set_mirror(slot, last, int(pos0[slot]) + acc)
                r.draft.set_mirror(slot, last, int(pos0[slot]) + acc)
                with self._hand_lock:
                    req, tokens = self._seqs[slot]
                tokens.extend(int(t) for t in g[:acc])
                accepted[req.id] = acc
                if req.first_tick is None:
                    req.first_tick = step_idx
                got = min(m, acc)
                req.draft_tokens += k
                req.accepted_tokens += got
                r.draft_tokens += k
                r.accepted_tokens += got
                accepted_this_tick += got
                telemetry.count("serving.accepted_tokens", got)
                if req.trace is not None:
                    req.trace.add("draft", t0, t_lock, step=step_idx,
                                  k=k, replica=r.index, slot=slot)
                    req.trace.add("verify", t_lock, t_tok, step=step_idx,
                                  accepted=acc, replica=r.index, slot=slot)
                done = False
                for _ in range(acc):
                    if r.mgr.consume(slot):
                        done = True
                if done:
                    with self._hand_lock:
                        del self._seqs[slot]
                    r.finish(req, tokens)
                    n_finished += 1
        # the verify's last column attended pos0 + k + 1 rows
        kv_tokens = sum(int(pos0[slot]) + k + 1 for slot in active)
        self._record_tick(step, ids, n_finished, adopted,
                          kv_tokens=kv_tokens, accepted=accepted,
                          n_parked=n_parked)
        telemetry.count("serving.draft_tokens", k * len(active))
        capacity.note_spec(r.index, k * len(active), accepted_this_tick)
        if r.draft_tokens:
            telemetry.gauge("serving.accept_rate",
                            round(r.accepted_tokens
                                  / r.draft_tokens, 4))


class Replica:
    """One model replica: engine + paged-KV manager + lane pair over
    one (tp) submesh, fed by a bounded internal queue."""

    def __init__(self, net, policy, index=0, mesh=None,
                 partition_rules=None, num_slots=4, int8=False,
                 block_size=16, num_blocks=None, queue_capacity=64,
                 max_prefill_tokens=None, summary_every=32, slo=None,
                 draft_net=None, spec_k=0, radix_cache=False,
                 prefix_cache_tokens=None):
        from .generative import LlamaServingEngine, refuse

        self.index = int(index)
        self.policy = policy
        self.spec_k = int(spec_k) if draft_net is not None else 0
        self.engine = LlamaServingEngine(
            net, max_len=policy.max_length, num_slots=num_slots,
            int8=int8, block_size=block_size, num_blocks=num_blocks,
            mesh=mesh, partition_rules=partition_rules,
            replica_id=self.index, spec_k=self.spec_k)
        self.draft = None
        if self.spec_k > 0:
            # the draft's pool is num_slots x max_blocks blocks and slot
            # s owns blocks s x max_blocks ... for life
            # (``draft_blocks``): fixed per-slot cache rows, no block
            # bookkeeping to keep consistent with the target's pool.
            # ``spec_k``: a draft is refused what speculation is
            self.draft = LlamaServingEngine(
                draft_net, max_len=policy.max_length,
                num_slots=num_slots, int8=int8, block_size=block_size,
                mesh=mesh, partition_rules=partition_rules,
                replica_id=self.index, spec_k=self.spec_k)
            self.draft.span_names = ("mxt.draft.dispatch",
                                     "mxt.draft.fetch")
        spec = self.engine.cache_spec
        itemsize = self.engine.cache_itemsize
        self.mgr = PagedKVCacheManager(
            num_slots, policy.max_length,
            num_blocks=self.engine.num_blocks,
            block_size=self.engine.block_size,
            kv_bytes_per_block=spec.kv_bytes_per_block(
                self.engine.block_size, itemsize),
            state_bytes_per_slot=spec.state_bytes_per_slot(itemsize))
        self.radix = None
        refuse(spec, radix=radix_cache)
        if radix_cache:
            from .radix import RadixPrefixCache
            cap = int(prefix_cache_tokens
                      if prefix_cache_tokens is not None
                      else self.engine.num_blocks
                      * self.engine.block_size // 2)
            self.radix = RadixPrefixCache(self.mgr.allocator,
                                          self.engine.block_size, cap)
            self.mgr.prefix_cache = self.radix
        self.draft_tokens = 0
        self.accepted_tokens = 0
        self.queue = RequestQueue(queue_capacity)
        self.max_prefill_tokens = int(max_prefill_tokens or
                                      policy.max_batch
                                      * policy.max_length)
        self.summary_every = int(summary_every)
        self.prefill = PrefillLane(self)
        self.decode = DecodeLane(self)
        self.capacity_evt = threading.Event()  # set on evict: re-admit
        self.slo = slo   # shared SLOTracker (metrics.py) or None
        # slot -> (t_free, request id, tick) of its last release: the
        # leaving request's ``t_done``, its id and ``engine.steps``
        # (release() writes it on the thread that frees the slot, the
        # prefill lane reads it as it admits into the slot)
        self.released = {}
        self.completed = 0
        self.failed = 0
        # decode steps booked, and those of them that were queued before
        # the step ahead of them had been fetched
        self.batches = 0
        self.steps_ahead = 0

    def draft_blocks(self, slot):
        """The draft pool's blocks that are ``slot``'s for life."""
        n = self.draft.max_blocks
        return list(range(slot * n, (slot + 1) * n))

    # -- dispatcher-facing ----------------------------------------------------
    def load(self):
        """Routing weight: tokens reserved in the KV pool plus tokens
        waiting in the internal queue."""
        queued = self.queue.queued_tokens(
            lambda r: len(r.prompt_ids) + r.max_new_tokens)
        return self.mgr.reserved_tokens() + queued

    def offer(self, req):
        ok = self.queue.offer(req)
        if ok:
            # accepted offers only: a shed request never joins the
            # arrival process the λ estimator models
            capacity.note_arrival(self.index, t=req.t_submit)
        return ok

    # -- lifecycle ------------------------------------------------------------
    def start(self):
        self.prefill.start()
        self.decode.start()

    def stop(self, drain=True):
        """Drain order matters: prefill first (with decode still live,
        so draining admissions can wait for blocks decode will free),
        then decode finishes the in-flight slot set."""
        self.queue.close()
        self.prefill.request_stop(drain)
        self.prefill.join()
        self.decode.request_stop()
        self.decode.join()
        for req in self.queue.take_group(lambda r: 0, 1 << 30):
            req.future.set_exception(
                ServerClosedError("server stopped before execution"))

    # -- completion -----------------------------------------------------------
    def release(self, req, step=None):
        """Free ``req``'s slot, its blocks and its mirrors.  The stamp of
        the release is the request's ``t_done``, taken and kept under
        the slot BEFORE the manager can hand the slot on, so the next
        admission into it finds its own predecessor (the ``slot.turn``
        record's ``t_free``, ``prev_request_id``, ``freed_by``).
        ``step``: the ``seq`` of the step whose booking frees it, where
        that is not the engine's newest."""
        req.t_done = time.perf_counter()
        if step is None:
            step = self.engine.steps
        self.released[req.slot] = (req.t_done, req.id, step)
        self.mgr.evict(req.slot)
        self.engine.clear_slot(req.slot)
        if self.draft is not None:
            self.draft.clear_slot(req.slot)

    def finish(self, req, tokens, step=None):
        if step is None:
            step = self.engine.steps
        self.release(req, step)
        self.capacity_evt.set()
        req.done_step = step
        n = req.max_new_tokens
        req.future.set_result(np.concatenate(
            [np.asarray(req.prompt_ids, np.int32),
             np.asarray(tokens[:n], np.int32)]))
        self.completed += 1
        telemetry.count("serving.completed")
        telemetry.count(f"serving.completed|replica={self.index}")
        capacity.note_completion(self.index, t=req.t_done)
        lane = "decode" if req.t_handoff is not None else "prefill"
        rec = req.record(lane=lane)
        tag = f"|replica={self.index}"
        if rec["queue_wait_ms"] is not None:
            telemetry.hist("serving.queue_wait_ms", rec["queue_wait_ms"])
            telemetry.hist("serving.queue_wait_ms" + tag,
                           rec["queue_wait_ms"])
        if rec["total_ms"] is not None:
            telemetry.hist("serving.total_ms", rec["total_ms"])
            telemetry.hist("serving.total_ms" + tag, rec["total_ms"])
        if rec.get("ttft_ms") is not None:
            telemetry.hist("serving.ttft_ms", rec["ttft_ms"])
            telemetry.hist("serving.ttft_ms" + tag, rec["ttft_ms"])
        if rec.get("tpot_ms") is not None:
            telemetry.hist("serving.tpot_ms", rec["tpot_ms"])
            telemetry.hist("serving.tpot_ms" + tag, rec["tpot_ms"])
        if self.slo is not None:
            rec["slo_met"] = self.slo.observe(
                tenant=req.tenant, ttft_ms=rec.get("ttft_ms"),
                tpot_ms=rec.get("tpot_ms"))
        telemetry.emit(rec)
        if req.trace is not None:
            req.trace.event("evict", replica=self.index, slot=req.slot)
            tracing.finish(req.trace, status="ok", replica=self.index,
                           lane=lane, request_id=req.id)
        if self.summary_every and self.completed % self.summary_every == 0:
            self.emit_summary()

    def fail(self, req, exc, lane):
        """Failure-path accounting: the ``status="error"`` request
        record (tagged replica + lane — the eviction/rejection paths
        used to drop both), the failed counters, and the trace seal."""
        self.failed += 1
        telemetry.count("serving.failed")
        telemetry.count(f"serving.failed|replica={self.index}")
        if req.t_done is None:    # it held no slot: release() stamps it
            req.t_done = time.perf_counter()
        telemetry.emit(req.record(lane=lane, status="error",
                                  error=repr(exc)))
        if req.trace is not None:
            tracing.finish(req.trace, status="error",
                           replica=self.index, lane=lane,
                           error=repr(exc), request_id=req.id)

    def emit_summary(self):
        rec = {
            "record": "serving.latency",
            "replica": self.index,
            "completed": self.completed,
            "failed": self.failed,
            "batches": self.batches,
            "steps_ahead_share": round(self.steps_ahead / self.batches, 4)
            if self.batches else None,
            "queue_wait_ms": telemetry.hist_summary("serving.queue_wait_ms"),
            "total_ms": telemetry.hist_summary("serving.total_ms"),
            "ttft_ms": telemetry.hist_summary("serving.ttft_ms"),
            "handoff_ms": telemetry.hist_summary("serving.handoff_ms"),
            "batch_size": telemetry.hist_summary("serving.batch_size"),
            "kv_cache": self.mgr.stats(),
        }
        # the summary path already paid for stats(): feed the pool's
        # fragmentation figure to the capacity trend estimator here
        capacity.note_kv(self.index,
                         self.mgr.allocator.free_blocks,
                         self.mgr.num_blocks,
                         fragmentation=rec["kv_cache"].get(
                             "fragmentation"))
        cap_view = capacity.snapshot(self.index)
        if cap_view is not None:
            rec["capacity"] = cap_view
        if self.draft is not None:
            rec["speculative"] = {
                "k": self.spec_k,
                "draft_tokens": self.draft_tokens,
                "accepted_tokens": self.accepted_tokens,
                "accept_rate": round(self.accepted_tokens
                                     / self.draft_tokens, 4)
                if self.draft_tokens else None,
            }
        if self.radix is not None:
            rec["radix_cache"] = self.radix.stats()
        telemetry.emit(rec)


class ReplicaDispatcher:
    """Routes the front queue to the least-loaded replica.

    One thread pops the FIFO head and offers it to the replica with the
    smallest :meth:`Replica.load` that has internal queue space; if all
    replica queues are full the head is held (client backpressure
    already happened at the front queue's bounded ``put``)."""

    def __init__(self, queue, replicas, poll_s=0.005):
        self.queue = queue
        self.replicas = list(replicas)
        self.poll_s = float(poll_s)
        self._held = None
        self._stop = threading.Event()
        self._drain = True
        self._thread = None
        self.error = None

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            name="mxt-dispatch",
                                            daemon=True)
            self._thread.start()

    def stop(self, drain=True):
        self._drain = drain
        self._stop.set()
        self.queue.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            raise self.error
        leftovers = ([self._held] if self._held is not None else []) \
            + self.queue.take_group(lambda r: 0, 1 << 30)
        self._held = None
        for req in leftovers:
            if drain:
                while not self._route(req):
                    time.sleep(self.poll_s)
            else:
                req.future.set_exception(
                    ServerClosedError("server stopped before execution"))

    def _route(self, req):
        for rep in sorted(self.replicas, key=lambda r: r.load()):
            if rep.offer(req):
                return True
        return False

    def _loop(self):
        # catches dispatcher bugs so the routing thread never dies
        # silently; re-raised at stop()
        try:
            self._run()
        except Exception as exc:
            self.error = exc
            tracing.incident("dispatcher_thread_error",
                             context={"error": repr(exc)})

    def _run(self):
        while not self._stop.is_set():
            if self._held is None:
                group = self.queue.take_group(lambda r: 0, 1)
                if not group:
                    self.queue.wait_for_item(self.poll_s)
                    continue
                self._held = group[0]
            if self._route(self._held):
                self._held = None
            else:
                time.sleep(self.poll_s)

"""Request/response protocol for the serving layer.

Reference: the C predict API (``c_predict_api.h``, SURVEY §3.5) is a
single-session, caller-threaded surface — one Predictor, one request at
a time.  The serving subsystem puts a queue/scheduler in front of it,
so the protocol objects here carry what the C API's stack frame used to
carry implicitly: identity, timing, and a completion handle.

A :class:`Request` is one unit of admitted work.  Its ``future`` (a
``concurrent.futures.Future``) is the caller's completion handle —
``future.result(timeout)`` in client glue is the intended wait point
(the same contract as async-checkpoint tickets; see docs/lint.md on why
``.result()`` is legal in eager glue but an error inside traced code).

Backpressure is explicit: a full queue raises
:class:`ServerOverloadedError` at submit time instead of buying
unbounded latency.  Clients treat it like HTTP 503 — back off and
retry.
"""
from __future__ import annotations

import itertools
import time
from concurrent.futures import Future

from ..base import MXNetError

__all__ = ["Request", "ServerOverloadedError", "ServerClosedError"]


class ServerOverloadedError(MXNetError):
    """The bounded request queue is full: the server sheds load at
    admission instead of queueing into unbounded latency.  Retry with
    backoff, or raise ``queue_capacity``."""


class ServerClosedError(MXNetError):
    """Submit after ``stop()`` (or before ``start()``)."""


_ids = itertools.count(1)


class Request:
    """One in-flight inference request.

    ``inputs`` maps input name → host numpy array for ONE example —
    the length-bucketed axis is ``length_axis`` (batch dim added by the
    scheduler).  Generative requests carry ``prompt_ids`` (1-D int32)
    and ``max_new_tokens`` instead.

    Timing fields are filled in as the request moves through the
    pipeline and land verbatim in the per-request telemetry record:
    ``t_submit`` → ``t_start`` (dequeued into a batch; the delta is
    ``queue_wait_ms``) → ``t_first`` (generative: first token emitted;
    delta from submit is ``ttft_ms``) → ``t_done``.  ``t_commit``: the
    prefill lane committed the prompt's K/V (with ``t_first`` where the
    prefill forward yields the first token; a block decoder's comes
    from the decode lane).  ``commits``: a block decoder's log of
    every commit, ``(position, token, the block's pass)``.
    ``selected``: a selecting (latent) model's ``(position, what each
    layer of the request's last decode step read: (layers, k)
    positions, -1 where fewer were visible)``.
    """

    __slots__ = ("id", "inputs", "length", "prompt_ids", "max_new_tokens",
                 "future", "t_submit", "t_start", "t_first", "t_done",
                 "batch_size", "bucket", "slot", "joined_step",
                 "first_tick", "done_step", "replica", "t_handoff",
                 "kv_blocks", "t_commit", "commits", "selected",
                 "trace", "tenant", "draft_tokens", "accepted_tokens",
                 "prefix_hit_tokens", "prefill_saved_ms")

    def __init__(self, inputs=None, length=None, prompt_ids=None,
                 max_new_tokens=None, tenant=None):
        self.id = next(_ids)
        self.inputs = inputs
        self.length = length
        self.prompt_ids = prompt_ids
        self.max_new_tokens = max_new_tokens
        self.future = Future()
        self.t_submit = time.perf_counter()
        self.t_start = None
        self.t_first = None
        self.t_done = None
        self.batch_size = None
        self.bucket = None
        self.slot = None
        self.joined_step = None
        # decode ticks (``engine.steps`` values, the ``seq`` of the lane
        # log's ``decode.tick`` records) that advanced the request: its
        # token i >= 1 reached the host at ``t_tok`` of tick
        # ``first_tick + i - 1`` — per-token stamps at no cost per token
        self.first_tick = None
        self.done_step = None
        # disaggregated-lane fields (paged path; see docs/observability.md)
        self.replica = None     # which dp replica served the request
        self.t_commit = None    # prefill committed the prompt's K/V
        self.t_handoff = None   # decode lane adopted the prefilled KV
        self.commits = None     # a block decoder's (position, token, pass)
        self.selected = None    # a selecting model's last step's reads
        self.kv_blocks = None   # the request's claim on the pool, in blocks
        # observability (r12): the request-scoped span context (a
        # telemetry.tracing.Trace, None while tracing is off — every
        # serving call site guards on that None) and the SLO tenant
        self.trace = None
        self.tenant = tenant
        # speculative decoding + radix prefix cache (r19)
        self.draft_tokens = 0        # draft proposals scored for us
        self.accepted_tokens = 0     # proposals the target agreed with
        self.prefix_hit_tokens = None  # prompt tokens reused from cache
        self.prefill_saved_ms = None   # estimated prefill ms not spent

    def tpot_ms(self):
        """Time-per-output-token: decode milliseconds per generated
        token AFTER the first (TTFT owns the first) — None until done,
        and None for 1-token requests (no decode interval exists)."""
        if self.t_first is None or self.t_done is None or \
                not self.max_new_tokens or self.max_new_tokens < 2:
            return None
        return (self.t_done - self.t_first) * 1e3 \
            / (self.max_new_tokens - 1)

    def record(self, kind="serving.request", lane=None, status="ok",
               error=None):
        """The per-request JSONL record (emitted on completion, and —
        with ``status="error"`` — on the failure paths, so rejected or
        evicted requests still land in the stream with their replica
        and lane)."""
        rec = {
            "record": kind,
            "request_id": self.id,
            "status": status,
            "bucket": self.bucket,
            "batch_size": self.batch_size,
            "queue_wait_ms": (self.t_start - self.t_submit) * 1e3
            if self.t_start is not None else None,
            "total_ms": (self.t_done - self.t_submit) * 1e3
            if self.t_done is not None else None,
        }
        if lane is not None:
            rec["lane"] = lane
        if error is not None:
            rec["error"] = error
        if self.tenant is not None:
            rec["tenant"] = self.tenant
        if self.trace is not None:
            rec["trace_id"] = self.trace.trace_id
        if self.t_first is not None:
            rec["ttft_ms"] = (self.t_first - self.t_submit) * 1e3
        tpot = self.tpot_ms()
        if tpot is not None:
            rec["tpot_ms"] = tpot
        if self.slot is not None:
            rec["slot"] = self.slot
            rec["joined_step"] = self.joined_step
            rec["first_tick"] = self.first_tick
            rec["done_step"] = self.done_step
        if self.replica is not None:
            rec["replica"] = self.replica
        if self.kv_blocks is not None:
            rec["kv_blocks"] = self.kv_blocks
        committed = self.t_first if self.t_commit is None else self.t_commit
        if self.t_handoff is not None and committed is not None:
            # prefill→decode KV handoff latency: the prompt's K/V
            # committed (with the first token, where the prefill forward
            # yields one) → decode lane adopted the slot
            rec["handoff_ms"] = (self.t_handoff - committed) * 1e3
        if self.t_first is not None and self.t_start is not None:
            # prompt-processing wall time (dequeue → first token): the
            # figure the radix prefix cache exists to shrink
            rec["prefill_ms"] = (self.t_first - self.t_start) * 1e3
        if self.draft_tokens:
            rec["draft_tokens"] = self.draft_tokens
            rec["accepted_tokens"] = self.accepted_tokens
            rec["accept_rate"] = round(self.accepted_tokens
                                       / self.draft_tokens, 4)
        if self.prefix_hit_tokens is not None:
            rec["prefix_hit_tokens"] = self.prefix_hit_tokens
        if self.prefill_saved_ms is not None:
            rec["prefill_saved_ms"] = round(self.prefill_saved_ms, 3)
        return rec

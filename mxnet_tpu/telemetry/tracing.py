"""Request-scoped distributed tracing + the SLO flight recorder.

The serving path is three threads deep (dispatcher → prefill lane →
decode lane, serving/lanes.py) and the r11 telemetry could only say
*that* a request was slow, not *where*: the per-request JSONL record is
flat.  This module gives every request a ``trace_id`` and an explicit
span context that the serving code threads across those boundaries by
carrying the :class:`Trace` object on the ``Request`` itself
(``req.trace``) — no thread-locals, because the whole point is that a
request changes threads twice before its first decode tick.

One completed trace is a connected parent→child span tree::

    request                          (root, span id 1)
    ├── queue        dispatcher wait + bucket dwell
    ├── prefill      prompt forward + KV commit   [replica, slot,
    │                                              kv_blocks, mates]
    ├── handoff      prefill→decode KV adoption
    ├── decode.step  one per decode tick          [step, batch]
    ├── ...
    └── evict        slot/block release           (zero-duration)

Spans are recorded **retroactively** wherever the serving path already
stamps timing fields (``t_submit``/``t_start``/``t_first``/…): the hot
decode tick pays one dict construction + list append per traced slot,
nothing else.  Completed traces go three places:

* a ``{"record": "trace", ...}`` JSONL record via ``telemetry.emit``
  (so ``tools/trace_report.py`` can rebuild the tree from the stream);
* the chrome-trace buffer of ``mxnet_tpu.profiler`` via
  ``record_span_event`` when that profiler runs — request spans beside
  its per-op dispatch events, on the host's ``perf_counter`` clock (NOT
  the device trace: what shares a file with the device planes is the
  ``mxt.*`` spans of the lane log below);
* the **flight recorder**: a bounded ring of recent completed traces,
  dumped to JSON by :func:`incident` on overload rejection, replica
  exception, or OOM (memwatch embeds :func:`recent` into its
  post-mortem), so a tail-latency incident is explainable after the
  fact.

The **lane log** (further down) is the always-on half: one bounded
ring of coarse records — a decode tick, a prefill batch, a stretch the
prefill lane spent gated, a slot's turn from its release to its next
tick, a train dispatch, a pause of the garbage collector — appended by
the thread that did the work, from ``perf_counter`` stamps taken once
at each boundary.  A serving lane takes the stamps that bound a turn's
host part on two clocks (:func:`clocks`): beside the wall's ``t_*`` the
lane thread's own CPU seconds (``c_*``), so that :func:`stalls` can say
of a turn that took far longer than its kind whether the collector ran,
the lane computed or the lane was kept off the CPU.
It needs no ``enable()``; the per-request span trees above stay opt-in.
The same boundaries open ``jax.profiler.TraceAnnotation`` spans named
``mxt.*`` (one atomic load while no profile runs), which land on
``/host:CPU`` of the xplane beside the device planes; a lane thread is
always under one of them, its waits too.

Cost contract (same as the rest of telemetry): disabled →
``start_trace`` is one module-boolean check returning None, and every
serving call site guards on ``req.trace is not None``; enabled → spans
are host-side dict/list work, never a device sync (tools/lint exempts
the ``tracing`` head via ``RECORDING_HEADS``).  ``MXNET_TRACING=1``
enables at import.
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import statistics
import sys
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = ["enable", "disable", "is_enabled", "start_trace", "finish",
           "recent", "clear", "dump", "incident", "Trace",
           "RECORDER_CAPACITY", "lane_record", "lane_log", "lane_state",
           "LaneClock", "LANE_LOG_CAPACITY", "LANE_TAIL", "clocks",
           "gc_stats", "stalls", "stall_totals", "GC_PAUSE_MIN_S",
           "STALL_MIN_S", "STALL_CAUSES"]

# -- state -------------------------------------------------------------------

_enabled = False
_trace_ids = itertools.count(1)

#: flight-recorder ring capacity (completed traces kept for dumps)
RECORDER_CAPACITY = 64

_ring_lock = threading.Lock()
_ring = deque(maxlen=RECORDER_CAPACITY)
_last_dump = {}   # reason -> monotonic stamp of the last dump
#: minimum seconds between two dumps for the SAME reason — an overload
#: storm writes one report, not one per rejected request
DUMP_INTERVAL_S = 5.0


def _telemetry():
    # the parent package imports this module at its own import time;
    # resolve it lazily through sys.modules to keep the cycle harmless
    return sys.modules.get("mxnet_tpu.telemetry")


# -- spans -------------------------------------------------------------------

class _LiveSpan:
    """Context-manager form for code that brackets a region itself
    (tests/tools; the serving hot paths use :meth:`Trace.add`)."""

    __slots__ = ("trace", "name", "parent", "tags", "_t0")

    def __init__(self, trace, name, parent, tags):
        self.trace = trace
        self.name = name
        self.parent = parent
        self.tags = tags

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.trace.add(self.name, self._t0, time.perf_counter(),
                       parent=self.parent, **(self.tags or {}))
        return False


class Trace:
    """One request's span collection.  Thread-safe by construction:
    span ids come from a per-trace ``itertools.count`` and completed
    spans are appended to a plain list — both atomic under CPython —
    so the three lane threads never contend on a lock."""

    __slots__ = ("trace_id", "request_id", "tenant", "t0", "wall0",
                 "spans", "_ids", "root_id")

    def __init__(self, trace_id, request_id=None, tenant=None):
        self.trace_id = trace_id
        self.request_id = request_id
        self.tenant = tenant
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.spans = []
        self._ids = itertools.count(1)
        self.root_id = next(self._ids)   # root "request" span == id 1;
        # it is appended at finish() so its duration covers everything

    def add(self, name, t0, t1, parent=None, **tags):
        """Record a completed span retroactively from two
        ``perf_counter`` stamps.  Returns the span id (usable as a
        ``parent`` for children)."""
        sid = next(self._ids)
        self.spans.append({
            "id": sid,
            "parent": self.root_id if parent is None else parent,
            "name": name,
            "ts": t0,
            "dur_ms": (t1 - t0) * 1e3,
            "thread": threading.current_thread().name,
            "tags": tags,
        })
        return sid

    def event(self, name, parent=None, **tags):
        """Zero-duration marker (e.g. ``evict``)."""
        now = time.perf_counter()
        return self.add(name, now, now, parent=parent, **tags)

    def span(self, name, parent=None, **tags):
        """``with trace.span("phase"):`` — live-timed child span."""
        return _LiveSpan(self, name, parent, tags)


def enable():
    """Turn request tracing on.  Independent of ``telemetry.enable`` so
    the tracing-on-vs-off A/B can hold the telemetry arm fixed; enable
    both to get trace records on the JSONL stream (``telemetry.emit``
    is a no-op while telemetry is off — the flight-recorder ring still
    fills either way)."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def is_enabled():
    return _enabled


def start_trace(request_id=None, tenant=None):
    """A fresh :class:`Trace` for one request — or None while tracing
    is disabled (call sites guard on the None, the near-zero path)."""
    if not _enabled:
        return None
    return Trace(f"{os.getpid():x}-{next(_trace_ids):06x}",
                 request_id=request_id, tenant=tenant)


def finish(trace, status="ok", **root_tags):
    """Seal ``trace``: close the root span over the trace's whole
    lifetime, emit the ``trace`` JSONL record, mirror every span into
    the profiler's chrome-trace buffer when profiling, and push the
    trace into the flight-recorder ring.  Returns the record dict."""
    if trace is None:
        return None
    t1 = time.perf_counter()
    trace.spans.append({
        "id": trace.root_id,
        "parent": None,
        "name": "request",
        "ts": trace.t0,
        "dur_ms": (t1 - trace.t0) * 1e3,
        "thread": threading.current_thread().name,
        "tags": root_tags,
    })
    record = {
        "record": "trace",
        "trace_id": trace.trace_id,
        "request_id": trace.request_id,
        "tenant": trace.tenant,
        "status": status,
        "wall_time": trace.wall0,
        "t0": trace.t0,
        "total_ms": (t1 - trace.t0) * 1e3,
        "spans": list(trace.spans),
    }
    tel = _telemetry()
    if tel is not None:
        tel.emit(record)
        tel.count("tracing.finished")
    prof = sys.modules.get("mxnet_tpu.profiler")
    if prof is not None and prof.is_running():
        for sp in record["spans"]:
            args = {"trace_id": trace.trace_id,
                    "request_id": trace.request_id}
            args.update(sp["tags"])
            prof.record_span_event(
                f"trace.{sp['name']}", sp["ts"], sp["dur_ms"] * 1e-3,
                cat="trace", args=args)
    with _ring_lock:
        _ring.append(record)
    return record


# -- flight recorder ---------------------------------------------------------

def recent(n=None):
    """The most recent completed trace records, oldest first (up to
    ``n``, default the whole ring)."""
    with _ring_lock:
        traces = list(_ring)
    return traces if n is None else traces[-int(n):]


def clear():
    """Empty the ring (tests)."""
    with _ring_lock:
        _ring.clear()
    _last_dump.clear()


def dump(path=None, reason="", context=None):
    """Write the flight record — reason, context, every ring trace and
    the last :data:`LANE_TAIL` lane records — to ``path`` (default
    ``MXNET_TRACE_DUMP`` or ``flight_record_<pid>.json`` in the cwd).
    Returns the path."""
    if path is None:
        path = os.environ.get("MXNET_TRACE_DUMP") \
            or f"flight_record_{os.getpid()}.json"
    report = {
        "record": "flight_recorder",
        "reason": reason,
        "wall_time": time.time(),
        "context": context or {},
        "traces": recent(),
        # which phase of which tick stood still: perf_counter stamps,
        # comparable with ``now`` below and with each other
        "now": time.perf_counter(),
        "lanes": lane_log()[-LANE_TAIL:],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2, default=str)
    tel = _telemetry()
    if tel is not None:
        tel.count("tracing.flight_dump")
    return path


def incident(reason, context=None, path=None):
    """The automatic dump hook for serving failure paths (overload
    rejection, replica exception, OOM).  Rate-limited per ``reason``
    (one dump per :data:`DUMP_INTERVAL_S`), never raises into the
    caller, returns the dump path or None when skipped."""
    if not _enabled:
        return None
    now = time.monotonic()
    with _ring_lock:
        last = _last_dump.get(reason)
        if last is not None and now - last < DUMP_INTERVAL_S:
            return None
        _last_dump[reason] = now
    try:
        return dump(path=path, reason=reason, context=context)
    except Exception:
        return None  # reporting never masks the original failure


# -- lane log ----------------------------------------------------------------

#: records the ring keeps (a 100 ms decode tick fills it in 27 minutes; a
#: replica flat out at 40 ticks and 25 admissions a second, each admission
#: a batch and a turn, in 3)
LANE_LOG_CAPACITY = 16384
#: lane records a flight-record dump carries
LANE_TAIL = 256

_lane_log = deque(maxlen=LANE_LOG_CAPACITY)
_lane_clocks = {}   # replica -> the prefill lane's LaneClock
#: first and last stamp of each record kind: what ``lane_log`` filters on
_LANE_SPAN = {"decode.tick": ("t_loop", "t_book"),
              "prefill.batch": ("t_start", "t_first"),
              "prefill.gated": ("t0", "t1"),
              "slot.turn": ("t_start", "t_tok"),
              "train.dispatch": ("t0", "t_end"),
              "gc.pause": ("t0", "t1")}


def clocks():
    """Now, on the two clocks a serving lane stamps the ends of a turn's
    host part with -> ``(perf_counter, thread_time)``: the wall's
    seconds (a record's ``t_*``) and the CPU seconds of the CALLING
    thread (its ``c_*``).  Between two stamps of one thread the wall's
    difference less the CPU's is the time that thread did not run: it
    waited for the device, a lock or the interpreter, or was not
    scheduled.  Only the stamps that :func:`stalls` or a reader of the
    benchmark takes a difference of are taken on both (``thread_time``
    is a system call, some 30 us in a serving process on a chip's host:
    PERF.md, PR 49)."""
    return time.perf_counter(), time.thread_time()


def lane_record(kind, **fields):
    """Append one record to the lane log.  Always on: a dict and a
    deque append (atomic under CPython, so the writers share no lock).
    Every stamp is a ``time.perf_counter()`` the caller took where the
    work happened; the schema of each ``kind`` is in
    docs/observability.md.  Returns the record, which a writer may
    complete later (a train dispatch's reported values arrive when they
    are fetched)."""
    fields["kind"] = kind
    _lane_log.append(fields)
    return fields


def lane_log(kind=None, since=None, until=None):
    """A copy of the lane log, oldest first.  ``kind`` keeps one record
    kind; ``since`` / ``until`` (``perf_counter`` seconds) keep the
    records that overlap ``[since, until)``: last stamp at or after
    ``since``, first stamp before ``until``."""
    while True:
        try:
            records = list(_lane_log)
            break
        except RuntimeError:   # a lane appended while we copied
            continue
    out = []
    for rec in records:
        if kind is not None and rec["kind"] != kind:
            continue
        first, last = _LANE_SPAN[rec["kind"]]
        if since is not None and rec[last] < since:
            continue
        if until is not None and rec[first] >= until:
            continue
        out.append(rec)
    return out


class LaneClock:
    """Where one prefill lane's wall time went since it was built:
    seconds ``busy`` (a batch in hand), ``gated`` (work queued but no
    slot, block or token budget for it) and ``idle`` (nothing queued).
    The lane thread calls :meth:`enter` at each change with a stamp it
    already holds, so the three always sum to the time since
    ``t_origin``.  Each stretch of ``gated`` is also written to the lane
    log when it ends, for readers that clip to a window."""

    MODES = ("busy", "gated", "idle")

    def __init__(self, replica):
        self.replica = replica
        self.t_origin = self._mark = time.perf_counter()
        self._mode = "idle"
        self._why = None
        self.seconds = dict.fromkeys(self.MODES, 0.0)
        self.gates = {}      # reason -> stretches of gated begun for it
        self.batches = 0
        _lane_clocks[replica] = self

    def enter(self, mode, t, why=None):
        """The lane is in ``mode`` from ``t`` on (``why``: what gates it)."""
        prev = self._mode
        self.seconds[prev] += t - self._mark
        self._mark = t
        if mode == prev:
            return
        if prev == "gated":
            lane_record("prefill.gated", replica=self.replica,
                        t0=self._gate0, t1=t, reason=self._why)
        elif mode == "gated":
            self._gate0, self._why = t, why
            self.gates[why] = self.gates.get(why, 0) + 1
        if mode == "busy":
            self.batches += 1
        self._mode = mode

    def snapshot(self):
        now = time.perf_counter()
        out = {f"{m}_s": s for m, s in self.seconds.items()}
        out[f"{self._mode}_s"] += now - self._mark   # the open stretch
        out.update(replica=self.replica, mode=self._mode,
                   wall_s=now - self.t_origin, gates=dict(self.gates),
                   batches=self.batches)
        return out


def lane_state(replica=0):
    """``busy_s`` / ``gated_s`` / ``idle_s`` of replica ``replica``'s
    prefill lane since its server was built, the count of gated
    stretches per reason and of batches; None before any server."""
    clock = _lane_clocks.get(replica)
    return None if clock is None else clock.snapshot()


# -- the collector's pauses ------------------------------------------------

#: a collection this long or longer is written to the lane log
GC_PAUSE_MIN_S = 1e-3

_gc_totals = {"runs": 0, "seconds": 0.0}
_gc_open = None     # (perf_counter, span) of the collection under way


def _on_gc(phase, info):
    """The module's one ``gc.callbacks`` entry, always on as the lane
    log is.  The interpreter calls it in the thread that set the
    collection off, which holds the interpreter's lock throughout: every
    Python thread of the process stands still from ``start`` to
    ``stop``.  Every collection counts (:func:`gc_stats`) and lies
    under an ``mxt.gc.pause`` span; one of :data:`GC_PAUSE_MIN_S` or
    longer is also a ``gc.pause`` lane record."""
    global _gc_open
    if phase == "start":
        span = TraceAnnotation("mxt.gc.pause",
                               generation=info["generation"])
        span.__enter__()
        _gc_open = (time.perf_counter(), span)
    elif _gc_open is not None:
        t1 = time.perf_counter()
        (t0, span), _gc_open = _gc_open, None
        span.__exit__(None, None, None)
        _gc_totals["runs"] += 1
        _gc_totals["seconds"] += t1 - t0
        if t1 - t0 >= GC_PAUSE_MIN_S:
            lane_record("gc.pause", t0=t0, t1=t1,
                        generation=info["generation"],
                        collected=info["collected"],
                        thread=threading.current_thread().name)


gc.callbacks.append(_on_gc)


def gc_stats():
    """Collections since import and the seconds they took, the short
    ones too: ``{"runs", "seconds"}``."""
    return dict(_gc_totals)


# -- stalls ----------------------------------------------------------------

#: a turn whose host part passes the median of its kind by more than this
#: has stalled.  The shortest stall on record is 55 ms; the host of a chip
#: counts CPU seconds in ticks of 10 ms, so half of a stall is more than
#: one stray tick from here on, and a lane's own tail (a batch's commit
#: reads up to 16 ms over its median) lies below it (PERF.md, PR 49)
STALL_MIN_S = 0.020
#: in the order they are tried
STALL_CAUSES = ("gc", "own", "offcpu")
#: turns of a kind under which their median says nothing
_STALL_MIN_TURNS = 5
# a kind's wall stamps in the order its thread passes them (a decode turn
# ends at the next turn's first), the phases between them, and those of
# them that are taken on the thread's CPU clock too: the host part's
# ends (a turn's second is the next turn's first) and the two ends of
# the wait for the device inside it
_TICK_STAMPS = ("loop", "lock", "disp0", "disp1", "tok", "book")
_TICK_PHASES = ("adopt", "lock", "dispatch", "fetch", "book", "tail")
_TICK_CPU = ("loop", "disp1", "tok")
_BATCH_STAMPS = ("start", "disp1", "ready", "lock", "commit1", "first")
_BATCH_PHASES = ("dispatch", "fetch", "ready", "commit", "tail")
_BATCH_CPU = ("start", "disp1", "ready", "first")


def _edges(rec, clock, stamps):
    """``rec``'s ``stamps`` on ``clock`` (``"t"`` / ``"c"``), or None
    where one is missing (an older record, a test's stub engine)."""
    out = [rec.get(f"{clock}_{s}") for s in stamps]
    return None if None in out else out


def _turns(since, until):
    """The lane log's decode turns and prefill batches that began in
    ``[since, until)`` and carry both clocks -> ``[(lane, phases,
    [turn])]``, a turn its wall edges (``t``; the lane's wait with
    nothing to step ``idle``) and the lane thread's CPU seconds over its
    host part (``cpu``)."""
    by_replica, turns, batches = {}, [], []
    for rec in lane_log("decode.tick", since, until):
        by_replica.setdefault(rec["replica"], []).append(rec)
    for recs in by_replica.values():      # each in log order: its lane's own
        for a, b in zip(recs, recs[1:]):
            t = _edges(a, "t", _TICK_STAMPS)
            c = _edges(a, "c", _TICK_CPU)
            if b["seq"] != a["seq"] + 1 or None in (t, c, b.get("c_loop")) \
                    or (since is not None and a["t_loop"] < since):
                continue
            turns.append(dict(
                rec=a, t=t + [b["t_loop"]], idle=b.get("idle_s", 0.0),
                cpu=(b["c_loop"] - c[0]) - (c[2] - c[1])
                - b.get("idle_cpu_s", 0.0)))
    for rec in lane_log("prefill.batch", since, until):
        t = _edges(rec, "t", _BATCH_STAMPS)
        c = _edges(rec, "c", _BATCH_CPU)
        if None in (t, c) or (since is not None and rec["t_start"] < since):
            continue
        batches.append(dict(rec=rec, t=t, idle=0.0,
                            cpu=(c[3] - c[0]) - (c[2] - c[1])))
    return [("decode", _TICK_PHASES, turns),
            ("prefill", _BATCH_PHASES, batches)]


def stalls(since=None, until=None):
    """The decode turns and prefill batches of the lane log (those that
    began in ``[since, until)``, ``perf_counter`` seconds) that took the
    host far longer than their kind, each with a cause -> a list of
    dicts, oldest first: ``lane`` (``"decode"`` / ``"prefill"``),
    ``replica``, ``seq``, ``phase``, ``t0``, ``wall_ms``, ``cpu_ms``,
    ``cause``.  The one place the rule and its constants live:
    ``server.stats()["lanes"][i]["stalls"]`` and the benchmark's
    ``stall_share.*`` readers call it.

    A turn's **host part** is its period less its wait for the device's
    tokens: for a decode turn ``(next t_loop - t_loop) - (t_tok -
    t_disp1)``, less ``idle_s`` (and ``idle_cpu_s`` of its CPU seconds)
    where the lane waited with nothing to step; for a batch ``(t_first
    - t_start) - (t_ready - t_disp1)``.  A turn **stalled** where its
    host part passes the median of its kind over the asked stretch by
    more than :data:`STALL_MIN_S`; the excess is the stall's length
    (``wall_ms``), ``phase`` the phase with the
    largest excess over that phase's own median (``adopt``, ``lock``,
    ``dispatch``, ``book``, ``tail``; a batch's ``dispatch``, ``ready``,
    ``commit``, ``tail``) and ``t0`` that phase's first stamp.  Its
    cause is the first of these that holds:

    * ``gc``: ``gc.pause`` records, of any thread, overlap the host
      part for at least half of the stall;
    * ``own``: the lane thread's own CPU seconds over the host part
      (``c_*`` of the same four stamps) pass their mean over the
      stretch's turns by at least half of the stall (``cpu_ms``): the
      lane computed, a long booking, a manager's count, a trace;
    * ``offcpu``: what is left.  The lane did not run and no collection
      did: another thread of the process held the interpreter's lock or
      a lock of the runtime (the other lane, a client thread, the
      profiler), or the machine ran nothing of the process (the host's
      other tenants, a throttled cgroup).  The process's own CPU clock
      does not tell the two apart where it ticks at 10 ms (PERF.md,
      PR 49), so neither is claimed.

    The thread's CPU clock may tick coarsely (10 ms on a chip's host),
    so what a turn is held against is the mean over the stretch, never
    a median of single turns' CPU seconds, which reads 0 or a tick.

    **What it cannot see**: a stall inside the wait for the device with
    nothing queued ahead cannot be told from a slow step on these
    clocks, and is not counted.  Records without the ``c_*`` fields,
    and a kind with fewer than five turns, give nothing."""
    pauses = [(r["t0"], r["t1"]) for r in lane_log("gc.pause", since, until)]
    found = []
    for lane, phases, turns in _turns(since, until):
        if len(turns) < _STALL_MIN_TURNS:
            continue
        wait = phases.index("fetch")
        for u in turns:
            wall = [t1 - t0 for t0, t1 in zip(u["t"], u["t"][1:])]
            wall[-1] -= u["idle"]
            u["wall"], u["host"] = wall, sum(wall) - wall[wait]
        med_host = statistics.median(u["host"] for u in turns)
        mean_cpu = statistics.fmean(u["cpu"] for u in turns)
        med_wall = [statistics.median(u["wall"][i] for u in turns)
                    for i in range(len(phases))]
        for u in turns:
            excess = u["host"] - med_host
            if excess <= STALL_MIN_S:
                continue
            over = [w - m for w, m in zip(u["wall"], med_wall)]
            over[wait] = float("-inf")
            at = over.index(max(over))
            t = u["t"]
            paused = sum(max(0.0, min(p1, hi) - max(p0, lo))
                         for p0, p1 in pauses
                         for lo, hi in ((t[0], t[wait]), (t[wait + 1], t[-1])))
            if paused >= excess / 2:
                cause = "gc"
            elif u["cpu"] - mean_cpu >= excess / 2:
                cause = "own"
            else:
                cause = "offcpu"
            found.append(dict(
                lane=lane, replica=u["rec"]["replica"], seq=u["rec"]["seq"],
                phase=phases[at], t0=t[at], wall_ms=excess * 1e3,
                cpu_ms=(u["cpu"] - mean_cpu) * 1e3, cause=cause))
    return sorted(found, key=lambda s: s["t0"])


def stall_totals(found):
    """An operator's view of :func:`stalls`' list: how many, the longest
    and the milliseconds by cause."""
    by_cause = dict.fromkeys(STALL_CAUSES, 0.0)
    for s in found:
        by_cause[s["cause"]] += s["wall_ms"]
    return {"count": len(found),
            "longest_ms": max((s["wall_ms"] for s in found), default=0.0),
            "ms_by_cause": by_cause}


if os.environ.get("MXNET_TRACING", "0") == "1":
    enable()

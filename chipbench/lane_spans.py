"""What the span readers share.  The program keeps an always-on, bounded log
of its lanes' coarse records (a decode tick, a prefill batch, a stretch the
prefill lane spent gated, a train dispatch), every stamp a ``perf_counter``
like ``obs["t0_abs"]``; it outlives the server, so the readers take it
in-process after the window.  This file knows one program name,
``mxnet_tpu.telemetry.tracing.lane_log``; a program without it (an older
commit) gives every reader nothing to read, and the reader returns ``None``.
"""
from __future__ import annotations

import statistics

#: a tick's period, split at the lane's stamps (and the next tick's first)
TICK_STAMPS = ("t_loop", "t_lock", "t_disp0", "t_disp1", "t_tok", "t_book")
TICK_PHASES = ("adopt", "lock", "dispatch", "fetch", "book", "tail")


def records(obs, kind, from_start=False):
    """The program's lane records of ``kind`` that overlap the window, oldest
    first (``from_start``: and everything before it)."""
    try:
        from mxnet_tpu.telemetry import tracing
        lane_log = tracing.lane_log
    except (ImportError, AttributeError):
        return []
    t0 = obs["t0_abs"]
    return lane_log(kind, since=None if from_start else t0,
                    until=t0 + obs["window_s"])


def _ticks_by_replica(obs, from_start=False):
    out = {}
    for rec in records(obs, "decode.tick", from_start):
        out.setdefault(rec["replica"], []).append(rec)
    return list(out.values())    # each in log order: its lane's own


def tick_phases_ms(obs):
    """Per pair of consecutive ticks of one replica, the tick's period split
    where the lane stamps it: adopt (top of the loop to asking for the device
    lock), lock (waiting for it), dispatch (uploads and the jitted call),
    fetch (waiting for the device's tokens), book (per-slot bookkeeping and
    finishing requests), tail (to the top of the next turn).  -> list of
    dicts of milliseconds, ``period`` and ``host`` (= period - fetch) among
    them."""
    out = []
    for ticks in _ticks_by_replica(obs):
        for a, b in zip(ticks, ticks[1:]):
            if b["seq"] != a["seq"] + 1 or a["t_loop"] < obs["t0_abs"]:
                continue
            edges = [a[s] for s in TICK_STAMPS] + [b["t_loop"]]
            row = {n: (e1 - e0) * 1e3
                   for n, e0, e1 in zip(TICK_PHASES, edges, edges[1:])}
            row["period"] = (b["t_loop"] - a["t_loop"]) * 1e3
            row["host"] = row["period"] - row["fetch"]
            out.append(row)
    return out


def tick_host_ms(obs):
    """Median over ticks of the part of a tick's period in which the host is
    not waiting for the device."""
    hosts = [row["host"] for row in tick_phases_ms(obs)]
    return statistics.median(hosts) if hosts else None


def token_gaps(obs):
    """-> {request id: [seconds between consecutive tokens]} for every token
    that reached the host inside the window: from the prefill batch's
    ``t_first`` to the first tick's ``t_tok``, then tick to tick.  ``None``
    where a tick commits a varying number of tokens (speculation)."""
    first = {rid: b["t_first"]
             for b in records(obs, "prefill.batch", from_start=True)
             for rid in b["request_ids"]}
    t0 = obs["t0_abs"]
    gaps = {}
    # from the log's start, so that a request active across the window's
    # start has the tick before it; a gap counts where it ends
    for ticks in _ticks_by_replica(obs, from_start=True):
        prev = None
        for tick in ticks:
            if "accepted" in tick:
                return None
            if prev is not None and prev["seq"] + 1 == tick["seq"]:
                held = set(prev["request_ids"])
            elif tick["n_adopted"] == tick["n_active"]:
                held = ()          # every request is new: no tick before it
            else:
                prev = tick        # the ring lost what came before
                continue
            if tick["t_tok"] >= t0:
                for rid in tick["request_ids"]:
                    before = prev["t_tok"] if rid in held else first.get(rid)
                    if before is not None:
                        gaps.setdefault(rid, []).append(tick["t_tok"] - before)
            prev = tick
    return gaps


def clipped_share(obs, kind, first, last):
    """Percent of the window that the union of the ``kind`` records' intervals
    [``first``, ``last``] covers, each clipped to the window."""
    recs = records(obs, kind)
    if not recs:
        return None
    lo, hi = obs["t0_abs"], obs["t0_abs"] + obs["window_s"]
    covered, edge = 0.0, lo
    for s, e in sorted((r[first], r[last]) for r in recs):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            covered += e - s
            edge = e
    return 100.0 * covered / obs["window_s"]

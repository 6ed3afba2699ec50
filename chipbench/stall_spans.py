"""What the readers of the host's two clocks share.  Since PR 49 the program
stamps the ends of a turn's host part in a ``decode.tick`` and a
``prefill.batch`` record (``lane_spans.records``) on the lane thread's CPU clock
(``c_*``) beside the wall's (``t_*``); it writes the collector's pauses of 1 ms
and longer as ``gc.pause`` records, and names the turns that took the host far
longer than their kind, each with a cause
(``mxnet_tpu.telemetry.tracing.stalls``: the rule and its constants are the
program's).  A program without the function, or a window whose records carry no
``c_*`` field, gives every reader here nothing, and it returns ``None``.

The host of a chip counts CPU seconds in ticks of 10 ms (my chip runs, PR 49:
every difference of two ``c_*`` stamps reads 0, 10, 20, ... ms), so one turn's
CPU seconds say nothing and a median of them reads 0 or a tick.  Over a window's
thousands of turns the ticks add up to what the lane computed, so
``tick_offcpu_ms`` takes MEANS, of the wall and of the CPU alike (a median of
the wall beside a mean of the CPU reads below zero where the turns are of two
kinds), over the turns that did not stall: those are ``stall_share``'s.
"""
from __future__ import annotations

import statistics

import lane_spans


def _stalls(obs):
    """The program's stalls of the window, or None from a program that names
    none."""
    try:
        from mxnet_tpu.telemetry.tracing import stalls
    except ImportError:
        return None
    return stalls(since=obs["t0_abs"], until=obs["t0_abs"] + obs["window_s"])


def clocked(obs, kind="decode.tick", field="c_loop"):
    """The window's ``kind`` records that carry ``field`` (and so the others of
    its clock), oldest first."""
    return [r for r in lane_spans.records(obs, kind) if r.get(field) is not None]


def _has_clocks(obs):
    """Whether the window's ticks or batches were stamped on the CPU clocks."""
    return bool(clocked(obs) or clocked(obs, "prefill.batch", "c_start"))


def tick_offcpu_ms(obs):
    """Over the window's turns that did not stall, the mean of the host part's
    wall time (the turn's period less its wait for the device's tokens, as
    ``lane_spans.tick_host_ms`` takes it) less the mean of the lane thread's CPU
    seconds over the same stretches, in milliseconds: the part of
    ``tick_host_ms`` in which the lane neither waited for the device nor ran."""
    found = _stalls(obs)
    if found is None:
        return None
    stalled = {(s["replica"], s["seq"]) for s in found if s["lane"] == "decode"}
    by_replica = {}
    for rec in clocked(obs):
        by_replica.setdefault(rec["replica"], []).append(rec)
    kept = [((b["t_loop"] - a["t_loop"]) - (a["t_tok"] - a["t_disp1"]),
             (b["c_loop"] - a["c_loop"]) - (a["c_tok"] - a["c_disp1"]))
            for ticks in by_replica.values() for a, b in zip(ticks, ticks[1:])
            if b["seq"] == a["seq"] + 1 and a["t_loop"] >= obs["t0_abs"]
            and (a["replica"], a["seq"]) not in stalled]
    if not kept:
        return None
    wall, cpu = zip(*kept)
    return (statistics.fmean(wall) - statistics.fmean(cpu)) * 1e3


def stall_share(obs, causes=None):
    """Percent of the window that the stalls of both lanes with one of ``causes``
    (None: whatever the cause) lasted, their lengths summed; 0.0 in a window
    that held none."""
    found = _stalls(obs)
    if found is None or not _has_clocks(obs):
        return None
    ms = sum(s["wall_ms"] for s in found
             if causes is None or s["cause"] in causes)
    return 100.0 * ms * 1e-3 / obs["window_s"]


def gc_pause_max_ms(obs):
    """The longest ``gc.pause`` that overlaps the window, in milliseconds; 0.0
    where no collection reached the millisecond that makes it a record."""
    if not _has_clocks(obs):
        return None
    return max(((p["t1"] - p["t0"]) * 1e3
                for p in lane_spans.records(obs, "gc.pause")), default=0.0)

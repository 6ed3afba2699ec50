"""Arithmetic the metric readers share: percentiles, and what they take from a
reduced trace (``trace_reduce.reduce``)."""
from __future__ import annotations

import re
import statistics


def percentile(values, q):
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(r, obs):
    """From the moment the request was due (sent, in a closed loop)."""
    origin = r["due"] if r["due"] is not None else r["sent"]
    if r["t_first"] is None:
        return (obs["drained_s"] - origin) * 1e3    # failed: it waited to the end
    return (r["t_first"] - origin) * 1e3


def tpot_ms(r):
    if not r["finished"] or r["n_out"] < 2:
        return None
    return (r["t_done"] - r["t_first"]) * 1e3 / (r["n_out"] - 1)


def module_durations(obs, program, chip=0):
    """Device seconds of every execution of a named program in the trace."""
    if obs.get("trace") is None:
        return []
    pat = re.compile(obs["programs"][program])
    out = []
    for name, durs in obs["trace"]["chips"][chip]["modules"].items():
        if pat.search(name):
            out.extend(durs)
    return out


def median_module_ms(obs, program):
    d = module_durations(obs, program)
    return statistics.median(d) * 1e3 if d else None


def worst_idle_share(obs):
    if obs.get("trace") is None:
        return None
    t = obs["trace"]
    chips = sorted(t["chips"])[:obs["chips"]]
    return 100.0 * max(t["chips"][c]["idle_s"] for c in chips) / t["window_s"]


def in_traced_window(obs, t_rel):
    """Is a stamp (seconds from the window's start) inside the traced part?"""
    w = obs.get("trace_host_window")
    if w is None or t_rel is None:
        return False
    return w[0] <= obs["t0_abs"] + t_rel <= w[1]


def decode_tick_load(obs):
    """Mean active slots and mean cached tokens read, per decode tick."""
    ticks = obs["ticks"]
    if not ticks:
        return None
    dec_tokens = kv_tokens = 0
    for r in obs["requests"]:
        d = max(0, r["emitted"] - 1)            # the first token is prefill's
        dec_tokens += d
        kv_tokens += d * r["n_prompt"] + d * (d + 1) // 2
    return dec_tokens / ticks, kv_tokens / ticks

"""From a ``jax.profiler`` trace (xplane) to numbers.  Pure functions over
intervals, so that every PR computes the same number in the same way.

What a TPU trace holds (looked at by hand, v5e, jax 0.9.0): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event per HLO
instruction, named by its full text ``%name = type opcode(...)``) and ``Async
XLA Ops``; and the plane ``/host:CPU`` with one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.  Times are
nanoseconds from the start of the profile.  The host's and the device's clocks
agree only to a millisecond or two (in the recorded probe a program starts on
the device 1.1 ms before the host span that dispatched it opens), so a gap is
attributed to host spans only coarsely.
"""
from __future__ import annotations

import re

_COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start|-done)?\(")
_NAME = re.compile(r"^%([^\s=]+)\s*=")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.\d+)?$")
WINDOW_SPAN = "bench.trace_window"


def short_name(op_text):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _NAME.match(op_text)
    return m.group(1) if m else op_text[:48]


def is_collective(op_text):
    return bool(_COLLECTIVE.search(op_text))


def is_mosaic(op_text):
    return 'custom_call_target="tpu_custom_call"' in op_text


def load(path):
    """-> {"devices": {chip: {"ops", "modules", "async"}}, "host": [...]};
    every event a tuple (start_s, end_s, name), host events with the thread's
    line name as a fourth field."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dev[key].append((s, s + e.duration_ns * 1e-9, e.name))
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = e.start_ns * 1e-9
                        host.append((s, s + e.duration_ns * 1e-9, e.name, line.name))
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged, sorted, disjoint (start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given the busy union."""
    return subtract([(lo, hi)], busy)


def attribute(gap_list, host_spans):
    """Each gap goes to the host span that covers most of it (``unspanned``
    where none does).  -> {span name: seconds}, and the gaps with their names."""
    by_name, named = {}, []
    spans = [h for h in host_spans if h[2] != WINDOW_SPAN]
    for s, e in gap_list:
        best, cover = "unspanned", 0.0
        for hs, he, name, *_ in spans:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = name, c
        by_name[best] = by_name.get(best, 0.0) + (e - s)
        named.append((best, e - s))
    return by_name, named


def window_of(trace):
    """The traced window: the ``bench.trace_window`` host span if the harness
    wrote one, else from the first to the last device event."""
    for s, e, name, *_ in trace["host"]:
        if name == WINDOW_SPAN:
            return s, e
    evs = [ev for d in trace["devices"].values() for ev in d["ops"] + d["modules"]]
    return min(e[0] for e in evs), max(e[1] for e in evs)


def reduce(trace):
    """-> the summary every per-layer reader takes its numbers from."""
    lo, hi = window_of(trace)
    out = {"window_s": hi - lo, "chips": {}, "lo": lo, "hi": hi}
    host = clip(trace["host"], lo, hi)
    for chip, dev in sorted(trace["devices"].items()):
        ops = clip(dev["ops"], lo, hi)
        busy = union(ops)
        coll = union([o for o in ops if is_collective(o[2])] +
                     [o for o in clip(dev["async"], lo, hi) if is_collective(o[2])])
        compute = union([o for o in ops if not is_collective(o[2])])
        idle = gaps(busy, lo, hi)
        by_span, named = attribute(idle, host)
        sums, counts = {}, {}
        for s, e, name in ops:
            k = short_name(name)
            if _CONTROL_FLOW.match(k):
                continue   # covers its body's operations, which are events too
            sums[k] = sums.get(k, 0.0) + (e - s)
            counts[k] = counts.get(k, 0) + 1
        modules = {}
        for s, e, name in clip(dev["modules"], lo, hi):
            modules.setdefault(name.split("(")[0], []).append(e - s)
        mosaic = {}
        for s, e, name in ops:
            if is_mosaic(name):
                mosaic.setdefault(mosaic_kind(name), []).append(e - s)
        out["chips"][chip] = {
            "busy_s": total(busy), "idle_s": total(idle),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, compute)),
            "op_seconds": sums, "op_counts": counts, "modules": modules,
            "mosaic": mosaic, "idle_by_span": by_span,
            "longest_gaps": sorted(named, key=lambda g: -g[1])[:10]}
    return out


def mosaic_kind(op_text):
    """Which of the flash-attention kernels a Mosaic custom call is, read from
    what it returns: forward (output and float32 log-sum-exp), dkv (two
    gradients) or dq (one).  The kernels have no stable names yet."""
    m = re.match(r"^%[^\s=]+\s*=\s*(\(.*?\)|\S+)\s+custom-call\(", op_text)
    outs = m.group(1) if m else ""
    n = outs.count("[")
    if n >= 2 and "f32[" in outs:
        return "flash_fwd"
    if n >= 2:
        return "flash_dkv"
    return "flash_dq" if "transpose" in short_name(op_text) else "flash_fwd_nolse"


def breakdown(summary, top=10):
    """The contract's ``breakdown``: device operations that took most time
    (summed over chips and over the instances of one kind) and the longest idle gaps by host span (worst chip)."""
    ops = {}
    for c in summary["chips"].values():
        for k, v in c["op_seconds"].items():
            k = re.sub(r"\.\d+$", "", k)    # fusion.12 and fusion.13 are one kind
            ops[k] = ops.get(k, 0.0) + v
    worst = max(summary["chips"].values(), key=lambda c: c["idle_s"])
    return {"device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, s] for n, s in worst["longest_gaps"][:top]]}

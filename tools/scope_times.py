#!/usr/bin/env python
"""Device seconds of a ``jax.profiler`` trace by ``jax.named_scope``, a
program at a time.

    python tools/scope_times.py <trace.xplane.pb> [--hlo <text>] dsa_scoring ...

An ``XLA Ops`` event is named by its HLO instruction's text and lies inside
the ``XLA Modules`` event of the program that ran it (one chip runs one
program at a time).  Every instruction goes to the first of the given scopes
that its text or its stats name (the scopes a ``jax.named_scope`` pushed are
path components of the instruction's ``op_name``), else to ``other``; control
flow (``while``, ``conditional``, ``call``) is left out: its body's
instructions are events too.  Where the events carry no ``op_name`` (v5e,
jax 0.9.0: the stats are the device's clock alone and the text has no
metadata), ``--hlo <after_optimizations.txt>`` gives ONE program's compiled
text (``XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text"``), whose
instructions do, and an event goes by its instruction's name (a fusion by
its root's scope): read that program's entry, the others' names differ.  One JSON object to stdout:
``{program: {"calls", "seconds", "by_scope": {scope: seconds}, "by_op":
{instruction: seconds}}}`` and, under ``"stat_keys"``, the stats an event
had.  The functions are importable: ``by_scope(path, scopes, hlo=None)``.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "chipbench"))


def scopes_of(hlo_text, scopes):
    """Instruction name -> the first of ``scopes`` its ``op_name`` holds,
    from a compiled module's text."""
    named = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", line)
        if m:
            scope = next((s for s in scopes if s in m.group(2)), None)
            if scope:
                named[m.group(1)] = scope
    return named


def by_scope(path, scopes, hlo=None):
    from jax.profiler import ProfileData

    import trace_reduce as tr

    named = scopes_of(open(hlo).read(), scopes) if hlo else {}
    out, keys = {}, set()
    for plane in ProfileData.from_file(path).planes:
        if not tr._DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                         for e in lines["XLA Modules"].events)
        starts = [m[0] for m in modules]
        for _s, _e, name in modules:
            prog = out.setdefault(name, {"calls": 0, "seconds": 0.0,
                                         "by_scope": {}, "by_op": {}})
            prog["calls"] += 1
        for e in lines["XLA Ops"].events:
            op = tr.short_name(e.name)
            if tr._CONTROL_FLOW.match(op):
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i < 0 or e.start_ns >= modules[i][1]:
                continue
            stats = [(str(k), str(v)) for k, v in e.stats]
            keys.update(k for k, _v in stats)
            text = e.name + " " + " ".join(v for _k, v in stats)
            scope = next((s for s in scopes if s in text),
                         named.get(op, "other"))
            prog, took = out[modules[i][2]], e.duration_ns * 1e-9
            prog["seconds"] += took
            prog["by_scope"][scope] = prog["by_scope"].get(scope, 0.0) + took
            prog["by_op"][op] = prog["by_op"].get(op, 0.0) + took
    out["stat_keys"] = sorted(keys)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("scopes", nargs="+")
    ap.add_argument("--hlo")
    a = ap.parse_args()
    print(json.dumps(by_scope(a.trace, a.scopes, a.hlo), indent=1,
                     sort_keys=True))

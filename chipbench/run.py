#!/usr/bin/env python3
"""chipbench: one run of one cell.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds everything by name and holds nothing about any one cell:
``BENCHMARK.json`` (the cell's configuration, traffic mix and chips, and which
metrics it reports), ``configs/<config>.json`` (sizes, family, reference),
``traffic/<traffic>.json`` (the mix), ``families/<family>.py`` (builds the
system under test), ``references/`` (the plain reference that decides
``correct``), ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py`` (one
reader per metric) and ``peaks.json``.

It fails, with no result line, when jax finds no TPU or fewer chips than the
cell asks for, and when anything compiled inside the timed window.  Facts of
the run go on earlier lines; the last line of standard output is the
contract's one JSON object.  ``--control 1`` (not part of the contract; used
when a limit is set and by the tests) also reads the lower-precision control.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
import time

T_IMPORT = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
TRACE_SECONDS = 4.0

if HERE not in sys.path:
    sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _since_process_start():
    """Seconds this process had lived when the module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


PRE_IMPORT_S = _since_process_start()

from host_watch import HostWatch  # noqa: E402  (after sys.path holds HERE)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def say(tag, obj):
    print(f"{tag}: {json.dumps(obj, sort_keys=True, default=float)}", flush=True)


class Phases:
    """Wall seconds and compile-pipeline seconds of each named phase."""

    def __init__(self, clock):
        self.clock, self.rows = clock, {}

    def __call__(self, name):
        return _Phase(self, name)


class _Phase:
    def __init__(self, owner, name):
        self.o, self.name = owner, name

    def __enter__(self):
        self.o.clock.lap()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        row = self.o.clock.lap()
        row["wall_s"] = round(time.perf_counter() - self.t, 3)
        self.o.rows[self.name] = row
        return False


def find_cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench, group, cell, e2e_reported=None):
    """The metrics of ``group`` that this cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if cells is None and group == "per_layer" and m["moves"] not in e2e_reported:
            continue
        out.append(m)
    return out


def _tracer(start_after, seconds, done):
    """Trace ``seconds`` of the window from a thread of its own, with a host
    span that marks the traced interval on the profile's clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # our own spans only: the python tracer's
    opts.host_tracer_level = 2     # events make stop_trace take many seconds
    time.sleep(start_after)
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            time.sleep(seconds)
    finally:
        done["traced_s"] = time.perf_counter() - t0
        done["host_window"] = (t0, t0 + done["traced_s"])
        jax.profiler.stop_trace()
        done["stop_trace_s"] = time.perf_counter() - t0 - done["traced_s"]


def set_up(workload, seed, seconds, require_tpu=True, data_dir=None):
    """Everything before the window: find the cell's files, look for the chip,
    build the system under test and warm its shapes.  ``sweep.py`` shares it."""
    data_dir = data_dir or HERE
    bench = load_json(ROOT if data_dir == HERE else data_dir, "BENCHMARK.json")
    cell_row = find_cell(bench, workload)
    chips = int(cell_row["chips"])

    import jax

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise SystemExit(f"chipbench: needs a TPU; jax found platform "
                             f"{devs[0].platform!r} ({len(devs)} device(s))")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: {workload} needs {chips} chips, "
                             f"jax found {len(devs)}")
    peaks = load_json(HERE, "peaks.json").get(devs[0].device_kind)
    if peaks is None and require_tpu:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{devs[0].device_kind!r} in peaks.json")

    import mxnet_tpu  # noqa: F401  the program; also places jax's compile cache

    imports_s = time.perf_counter() - T_IMPORT

    from compile_clock import CompileClock
    import traffic

    config = load_json(data_dir, "configs", cell_row["config"] + ".json")
    mix = load_json(data_dir, "traffic", cell_row["traffic"] + ".json")
    family = load_module(os.path.join(HERE, "families", config["family"] + ".py"),
                         "chipbench_family_" + config["family"])
    reference = load_module(os.path.join(HERE, config["reference"]),
                            "chipbench_reference_" + config["family"])

    def span(name):
        return jax.profiler.TraceAnnotation(name)

    clock = CompileClock()
    with clock:
        phases = Phases(clock)
        cell = family.Cell(config, mix, seed, chips, span, reference)
        with phases("traffic"):
            work = traffic.make_work(mix, seed, config["vocab_size"], seconds, chips)
        cell.build(phases, work)

    return {"bench": bench, "config": config, "mix": mix, "devs": devs,
            "peaks": peaks, "cell": cell, "work": work, "phases": phases,
            "chips": chips, "clock": clock, "imports_s": imports_s}


def run(argv=None, require_tpu=True, data_dir=None):
    """One run.  ``require_tpu=False`` and ``data_dir`` are for the tests under
    ``tests/``: the first skips the look for a chip, the second holds a test's
    own BENCHMARK.json, configs/ and traffic/ at sizes a CPU can run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    ctx = set_up(args.workload, args.seed, args.seconds, require_tpu, data_dir)
    bench, config, mix, devs, peaks = (ctx[k] for k in
                                       ("bench", "config", "mix", "devs", "peaks"))
    cell, work, phases, chips = (ctx[k] for k in ("cell", "work", "phases", "chips"))

    with ctx["clock"] as clock:
        tracer, traced = None, {}
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            tracer = threading.Thread(
                target=_tracer, daemon=True,
                args=(min(args.seconds * 0.4, 15.0),
                      min(TRACE_SECONDS, args.seconds * 0.4), traced))
        clock.lap()
        setup_s = PRE_IMPORT_S + (time.perf_counter() - T_IMPORT)
        if tracer:
            tracer.start()
        with HostWatch() as host:
            obs = cell.window(args.seconds, work)
        if tracer:
            tracer.join(timeout=300)
        in_window = clock.lap()
        mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devs[:chips])
        cell.end_window()

        t_chk = time.perf_counter()
        compared = cell.check(bool(args.control))
        check_s = time.perf_counter() - t_chk
        cell.finish()
        check_compile = clock.lap()

    say("setup_phases", {"pre_import_s": round(PRE_IMPORT_S, 3),
                         "imports_s": round(ctx["imports_s"], 3), **phases.rows})
    say("window_compiles", in_window)
    say("host", host.report)
    say("check", {"seconds": round(check_s, 3), **check_compile})
    if in_window["compiles"] or in_window["cache_misses"]:
        raise SystemExit(f"chipbench: {in_window['compiles']} program(s) compiled "
                         "inside the timed window: a shape was not warmed")

    # a row without a limit is a reading, shown beside the ones that decide
    correct = any(limit is not None for _n, _v, limit in compared)
    for name, value, limit in compared:
        ok = None if limit is None else bool(value <= limit)
        correct = correct and ok is not False
        print(f"compared: {name} = {value!r} limit {limit!r} "
              f"{'-' if ok is None else 'ok' if ok else 'FAILED'}", flush=True)
    if obs["attempted"] == 0:
        correct = False

    obs.update(config=config, mix=mix, peaks=peaks, chips=chips, setup_s=setup_s,
               programs=cell.programs, trace=None,
               trace_host_window=traced.get("host_window"))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": {}, "device": device}
    if args.trace:
        import glob

        import trace_reduce

        paths = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise SystemExit("chipbench: the profiler wrote no trace")
        summary = trace_reduce.reduce(trace_reduce.load(paths[0]))
        used = [summary["chips"][c] for c in sorted(summary["chips"])[:chips]]
        if not used or not any(c["busy_s"] > 0 for c in used):
            if require_tpu:
                raise SystemExit("chipbench: no operation ran on the device in "
                                 "the traced window")
        else:
            obs["trace"] = summary
            device["busy_s"] = statistics.fmean(c["busy_s"] for c in used)
            device["window_s"] = summary["window_s"]
            result["breakdown"] = trace_reduce.breakdown(summary)
        say("tracer", traced)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    group = "per_layer" if args.trace else "end_to_end"
    e2e = {m["name"] for m in metrics_for(bench, "end_to_end", args.workload)}
    for m in metrics_for(bench, group, args.workload, e2e):
        reader = load_module(os.path.join(
            HERE, "layer_metrics" if args.trace else "end_to_end",
            m["name"] + ".py"), "chipbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(obs)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    facts = {k: v for k, v in obs.items()
             if k not in ("requests", "config", "mix", "peaks", "trace", "programs",
                          "trace_host_window")}
    say("facts", facts)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    run()

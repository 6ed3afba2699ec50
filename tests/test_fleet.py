"""Fleet-wide training observability (ISSUE 13): rank-aware step
records, the stride-gated fleet exchange, the straggler/anomaly
watchdog, the training flight recorder, and the fleet report CLI.

Coverage map:
  * watchdog math as pure functions (skew / NaN / spike / regression,
    K-consecutive-window streaks);
  * in-process single-rank behavior: rank/world stamping, fleet views
    at the stride, anomaly records + counters + callback/halt, ring
    bounds, rate-limited dumps, /metrics == telemetry counters;
  * disabled-path guards (fleet off = one boolean check; PR 2/12
    pattern);
  * read_jsonl multi-path/glob merge by (step, rank);
  * SIGTERM-drain dump roundtrip through tools/fleet_report.py;
  * the dp2 CPU-mesh chaos lane: a SLOW_RANK-hooked straggler must be
    NAMED in the fleet view, the anomaly stream and the report CLI,
    and a SIGKILL'd rank must leave a readable flight dump.
"""
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, gluon, nd, telemetry
from mxnet_tpu.gluon import trainer as trainer_mod
from mxnet_tpu.telemetry import fleet
from mxnet_tpu.telemetry.sinks import ListSink, read_jsonl

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
WORKER = os.path.join(REPO, "tests", "_preempt_worker.py")


def _fleet_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import fleet_report
    return fleet_report


@pytest.fixture(autouse=True)
def _clean_fleet():
    telemetry.disable()
    telemetry.reset()
    fleet.clear()
    yield
    telemetry.disable()
    telemetry.reset()
    fleet.clear()


# --- watchdog math (pure functions) -----------------------------------------

def test_detect_skew_names_outlier_ranks():
    assert fleet.detect_skew([10.0, 10.0, 25.0, 10.0], 1.5) == [2]
    assert fleet.detect_skew([10.0, 10.0, 10.0], 1.5) == []
    assert fleet.detect_skew([10.0, 50.0], 1.5) == [1]
    # degenerate inputs are quiet, never raising
    assert fleet.detect_skew([7.0], 1.5) == []
    assert fleet.detect_skew([], 1.5) == []
    assert fleet.detect_skew([0.0, 0.0, 0.0], 1.5) == []


def test_detect_nan_inf_and_nonnumbers():
    assert fleet.detect_nan(float("nan"))
    assert fleet.detect_nan(float("inf"))
    assert fleet.detect_nan(float("-inf"))
    assert fleet.detect_nan("not-a-number")
    assert not fleet.detect_nan(3.5)
    assert not fleet.detect_nan(0)


def test_detect_spike_respects_min_history():
    hist = [1.0] * 7
    assert not fleet.detect_spike(100.0, hist, factor=10, min_history=8)
    hist.append(1.0)
    assert fleet.detect_spike(100.0, hist, factor=10, min_history=8)
    assert not fleet.detect_spike(5.0, hist, factor=10, min_history=8)
    assert not fleet.detect_spike(100.0, [0.0] * 8, factor=10,
                                  min_history=8)


def test_watchdog_streak_fires_after_k_consecutive_windows():
    wd = fleet.Watchdog(skew_threshold=1.5, consecutive=3)
    skewed = {"compute_ms": [10.0, 50.0],
              "allreduce_wait_ms": [5.0, 5.0]}
    assert wd.observe_fleet(16, skewed) == []
    assert wd.observe_fleet(32, skewed) == []
    out = wd.observe_fleet(48, skewed)
    assert [a["kind"] for a in out] == ["straggler"]
    assert out[0]["culprit"] == 1
    assert out[0]["windows"] == 3
    assert out[0]["ratio"] > 1.5
    # a clean window resets the streak; re-skewing starts from scratch
    clean = {"compute_ms": [10.0, 10.0], "allreduce_wait_ms": [5.0, 5.0]}
    assert wd.observe_fleet(64, clean) == []
    assert wd.observe_fleet(80, skewed) == []


def test_watchdog_flags_allreduce_wait_skew_separately():
    wd = fleet.Watchdog(skew_threshold=1.5, consecutive=1)
    view = {"compute_ms": [10.0, 10.0],
            "allreduce_wait_ms": [50.0, 5.0]}
    out = wd.observe_fleet(16, view)
    assert [a["kind"] for a in out] == ["allreduce_wait_skew"]
    assert out[0]["culprit"] == 0


def test_watchdog_local_detectors():
    wd = fleet.Watchdog(min_history=4, spike_factor=10.0,
                        regression_factor=2.0)
    for _ in range(6):
        assert wd.observe_step({"loss": 1.0, "grad_norm": 1.0,
                                "step_ms": 10.0}) == []
    out = wd.observe_step({"loss": float("nan"), "grad_norm": 50.0,
                           "step_ms": 25.0})
    assert {a["kind"] for a in out} == \
        {"nan_loss", "grad_spike", "step_regression"}


# --- disabled path (PR 2/12 pattern) ----------------------------------------

class _PoisonLock:
    def __enter__(self):
        raise AssertionError("disabled fleet path took a lock")

    def __exit__(self, *exc):
        return False

    acquire = __enter__


def test_fleet_disabled_never_locks_or_mutates(monkeypatch):
    assert not fleet.is_enabled()
    monkeypatch.setattr(fleet, "_lock", _PoisonLock())
    monkeypatch.setattr(fleet, "_ring_lock", _PoisonLock())
    rec = {"step": 1, "step_ms": 5.0, "loss": float("nan")}
    fleet.on_step_record(rec)
    assert "rank" not in rec
    assert fleet.incident("anything") is None


def test_fleet_disabled_overhead_bounded():
    rec = {"step": 1, "step_ms": 5.0}
    t0 = time.perf_counter()
    for _ in range(10_000):
        fleet.on_step_record(rec)
    assert time.perf_counter() - t0 < 0.5


def test_exchange_tolerates_six_column_peers(monkeypatch):
    """Rows gathered from pre-r20 peers carry six floats (no
    duty_cycle); the view renders their duty cycle as 0.0 (unknown)
    instead of crashing or misaligning columns — the same back-compat
    contract the r17 first_nan_layer bump established."""
    import types

    # a fake 2-rank gather that STRIPS the 7th float, as an old peer's
    # packed vector would
    def gather(vec):
        return [list(vec)[:6], list(vec)[:6]]

    fake_pl = types.SimpleNamespace(process_gather_hostvec=gather)
    # patch the indirection point, not sys.modules: injecting a fake
    # mxnet_tpu.parallel would also flip world()'s cache-enable check
    monkeypatch.setattr(fleet, "_parallel", lambda: fake_pl)
    monkeypatch.setattr(fleet, "world", lambda: (0, 2))
    view = fleet._fleet_exchange(
        {"step": 7, "step_ms": 10.0,
         "counters": {"trainer.allreduce_wait_ms": 2.0}})
    assert view["world_size"] == 2
    assert view["duty_cycle"] == [0.0, 0.0]
    assert view["first_nan_layer"] == [-1, -1]
    assert view["compute_ms"] == [8.0, 8.0]


def test_exchange_seven_column_rows_carry_duty_cycle(monkeypatch):
    import types

    def gather(vec):
        return [list(vec), list(vec)]

    fake_pl = types.SimpleNamespace(process_gather_hostvec=gather)
    monkeypatch.setattr(fleet, "_parallel", lambda: fake_pl)
    monkeypatch.setattr(fleet, "world", lambda: (1, 2))
    view = fleet._fleet_exchange(
        {"step": 9, "step_ms": 10.0,
         "counters": {"trainer.allreduce_wait_ms": 2.0}})
    assert view["duty_cycle"] == [pytest.approx(0.8)] * 2


def test_telemetry_on_fleet_off_leaves_records_unstamped():
    telemetry.enable()
    sink = ListSink()
    telemetry.add_sink(sink)
    telemetry.step_begin()
    rec = telemetry.step_end(examples=4)
    assert rec is not None
    assert "rank" not in rec and "world_size" not in rec
    assert all(r.get("record") != "fleet" for r in sink.records)


# --- rank stamping + fleet views at the stride ------------------------------

def test_step_records_gain_rank_and_views_emit_at_stride():
    telemetry.enable()
    fleet.enable(stride=2)
    sink = ListSink()
    telemetry.add_sink(sink)
    for _ in range(5):
        telemetry.step_begin()
        telemetry.count("trainer.allreduce_wait_ms", 2.0)
        telemetry.step_end(examples=8, loss=0.5)
    steps = [r for r in sink.records if r.get("record") is None]
    assert len(steps) == 5
    assert all(r["rank"] == 0 and r["world_size"] == 1 for r in steps)
    views = [r for r in sink.records if r.get("record") == "fleet"]
    assert [v["step"] for v in views] == [2, 4]
    v = views[-1]
    assert v["world_size"] == 1 and v["stride"] == 2
    for col in ("step_ms", "allreduce_wait_ms", "compute_ms",
                "peak_live_bytes", "examples_per_sec", "duty_cycle"):
        assert len(v[col]) == 1, col
    assert v["allreduce_wait_ms"] == [2.0]
    assert v["compute_ms"][0] == pytest.approx(
        max(v["step_ms"][0] - 2.0, 0.0))
    # r20: the 7th exchanged float is compute_ms / step_ms in [0, 1]
    assert v["duty_cycle"][0] == pytest.approx(
        v["compute_ms"][0] / v["step_ms"][0], abs=1e-3)
    assert v["stragglers"] == []
    assert telemetry.counters()["fleet.exchange"] == 2
    assert fleet.last_view()["step"] == 4
    # the flight ring holds step records AND views
    ring = fleet.recent()
    assert sum(1 for r in ring if r.get("record") == "fleet") == 2
    assert sum(1 for r in ring if r.get("record") is None) == 5


# --- anomalies: emission, counters, callback, halt --------------------------

def test_nan_loss_anomaly_emitted_and_counted():
    telemetry.enable()
    fleet.enable(stride=10_000)
    sink = ListSink()
    telemetry.add_sink(sink)
    telemetry.step_begin()
    telemetry.step_end(examples=4, loss=float("nan"))
    anomalies = [r for r in sink.records if r.get("record") == "anomaly"]
    assert len(anomalies) == 1
    a = anomalies[0]
    assert a["kind"] == "nan_loss" and a["rank"] == 0 and a["step"] == 1
    c = telemetry.counters()
    assert c["fleet.anomaly"] == 1
    assert c["fleet.anomaly.nan_loss"] == 1
    assert any(r.get("record") == "anomaly" for r in fleet.recent())


def test_anomaly_callback_replaces_default_warning():
    seen = []
    telemetry.enable()
    fleet.enable(stride=10_000, on_anomaly=seen.append)
    telemetry.step_begin()
    telemetry.step_end(loss=float("inf"))
    assert [a["kind"] for a in seen] == ["nan_loss"]


def test_watchdog_halt_raises_at_step_boundary_and_dumps(tmp_path,
                                                         monkeypatch):
    dump = str(tmp_path / "halt.json")
    monkeypatch.setenv("MXNET_FLEET_DUMP", dump)
    telemetry.enable()
    fleet.enable(stride=10_000, halt=True)
    telemetry.step_begin()
    with pytest.raises(fleet.WatchdogHalt):
        telemetry.step_end(loss=float("nan"))
    assert fleet.halt_requested()
    with open(dump) as f:
        doc = json.load(f)
    assert doc["record"] == "flight_recorder"
    assert doc["kind"] == "fleet"
    assert doc["reason"] == "watchdog_halt"
    assert any(r.get("record") == "anomaly" for r in doc["records"])


# --- flight recorder: ring bounds, dumps, rate limit ------------------------

def test_ring_bounded_and_dump_roundtrip(tmp_path):
    telemetry.enable()
    # the watchdog never arms: an empty step lasts microseconds, so on a
    # busy machine one of the 12 armed ones runs past twice the median
    # and its ``step_regression`` anomaly record joins the ring
    fleet.enable(stride=10_000, ring=8, min_history=1_000)
    for _ in range(20):
        telemetry.step_begin()
        telemetry.step_end(examples=4)
    ring = fleet.recent()
    assert len(ring) == 8
    assert [r["step"] for r in ring] == list(range(13, 21))
    assert fleet.recent(3) == ring[-3:]
    path = fleet.dump(str(tmp_path / "d.json"), reason="manual",
                      context={"why": "test"})
    with open(path) as f:
        doc = json.load(f)
    assert doc["rank"] == 0 and doc["world_size"] == 1
    assert doc["context"] == {"why": "test"}
    assert len(doc["records"]) == 8


def test_incident_rate_limited_per_reason(tmp_path):
    telemetry.enable()
    fleet.enable(stride=10_000)
    telemetry.step_begin()
    telemetry.step_end()
    p1 = fleet.incident("restart", path=str(tmp_path / "a.json"))
    p2 = fleet.incident("restart", path=str(tmp_path / "b.json"))
    p3 = fleet.incident("other", path=str(tmp_path / "c.json"))
    assert p1 is not None and os.path.exists(p1)
    assert p2 is None  # throttled: same reason inside DUMP_INTERVAL_S
    assert p3 is not None  # distinct reason has its own limiter


def test_incident_never_raises(monkeypatch):
    telemetry.enable()
    fleet.enable(stride=10_000)

    def boom(*a, **k):
        raise OSError("disk gone")

    monkeypatch.setattr(fleet, "dump", boom)
    assert fleet.incident("restart") is None


def test_oom_postmortem_embeds_recent_steps(tmp_path):
    telemetry.enable()
    fleet.enable(stride=10_000)
    for _ in range(3):
        telemetry.step_begin()
        telemetry.step_end(examples=4)
    from mxnet_tpu.telemetry import memwatch
    report_path = str(tmp_path / "oom.json")
    memwatch.write_postmortem(path=report_path, context="test",
                              error="RESOURCE_EXHAUSTED (fake)")
    with open(report_path) as f:
        report = json.load(f)
    assert "recent_steps" in report
    assert [r["step"] for r in report["recent_steps"]] == [1, 2, 3]


# --- live /metrics endpoint --------------------------------------------------

def test_metrics_endpoint_scrape_equals_telemetry_counters():
    telemetry.enable()
    fleet.enable(stride=10_000, http_port=0)
    telemetry.count("trainer.allreduce_bytes", 1234)
    telemetry.count("fleet.unit_test", 3)
    url = fleet.metrics_url()
    assert url is not None
    body = urllib.request.urlopen(url + "/metrics",
                                  timeout=10).read().decode()
    # every telemetry counter appears verbatim on the scrape (the
    # acceptance: live /metrics == the job's telemetry counters)
    for name, value in telemetry.counters().items():
        fam = "mxt_" + name.replace(".", "_") + "_total"
        assert f"{fam} {int(value)}" in body, (fam, body)
    assert "mxt_fleet_rank 0" in body
    assert "mxt_fleet_world_size 1" in body
    health = json.loads(urllib.request.urlopen(
        url + "/healthz", timeout=10).read().decode())
    assert health["status"] == "ok" and health["rank"] == 0
    telemetry.disable()
    assert fleet.metrics_url() is None


# --- profiler bridge ---------------------------------------------------------

def test_profiler_span_args_carry_rank_when_fleet_on(tmp_path):
    from mxnet_tpu import profiler

    trace = str(tmp_path / "prof.json")
    profiler.set_config(filename=trace)
    profiler.dump(finished=True)
    telemetry.enable()
    fleet.enable(stride=10_000)
    profiler.set_state("run")
    try:
        with telemetry.span("trainer.step"):
            pass
    finally:
        profiler.dump(finished=True)
        telemetry.disable()
    events = json.load(open(trace))["traceEvents"]
    evt = next(e for e in events if e.get("cat") == "telemetry")
    assert str(evt["args"]["rank"]) == "0"
    assert str(evt["args"]["world_size"]) == "1"


# --- read_jsonl multi-path / glob merge -------------------------------------

def test_read_jsonl_merges_streams_by_step_and_rank(tmp_path):
    a, b = tmp_path / "fleet.rank0.jsonl", tmp_path / "fleet.rank1.jsonl"
    a.write_text("".join(json.dumps({"step": s, "rank": 0}) + "\n"
                         for s in (1, 2, 3)))
    b.write_text("".join(json.dumps({"step": s, "rank": 1}) + "\n"
                         for s in (1, 2, 3)))
    merged = read_jsonl([str(a), str(b)])
    assert [(r["step"], r["rank"]) for r in merged] == \
        [(1, 0), (1, 1), (2, 0), (2, 1), (3, 0), (3, 1)]
    assert not merged.truncated
    globbed = read_jsonl(str(tmp_path / "fleet.rank*.jsonl"))
    assert list(globbed) == list(merged)


def test_read_jsonl_merge_tolerates_one_truncated_tail(tmp_path):
    a, b = tmp_path / "r0.jsonl", tmp_path / "r1.jsonl"
    a.write_text(json.dumps({"step": 1, "rank": 0}) + "\n"
                 + '{"step": 2, "ran')  # writer died mid-record
    b.write_text(json.dumps({"step": 1, "rank": 1}) + "\n")
    merged = read_jsonl([str(a), str(b)])
    assert merged.truncated
    assert [(r["step"], r["rank"]) for r in merged] == [(1, 0), (1, 1)]
    # single-path behavior is unchanged
    single = read_jsonl(str(a))
    assert single.truncated and len(single) == 1


# --- SIGTERM-drain dump roundtrip through fleet_report ----------------------

def test_drain_dump_roundtrips_through_fleet_report(tmp_path, monkeypatch,
                                                    capsys):
    dump_tmpl = str(tmp_path / "drain.rank{rank}.json")
    monkeypatch.setenv("MXNET_FLEET_DUMP", dump_tmpl)
    telemetry.enable()
    fleet.enable(stride=2)
    net = gluon.nn.Dense(2)
    net.initialize(mx.init.Xavier())
    net(nd.ones((1, 3)))
    for _ in range(6):
        telemetry.step_begin()
        telemetry.count("trainer.allreduce_wait_ms", 1.0)
        telemetry.step_end(examples=4, loss=0.25)
    with pytest.raises(SystemExit) as ei:
        checkpoint.drain_checkpoint_and_exit(str(tmp_path / "ck"), 6, net)
    assert ei.value.code == trainer_mod.PREEMPTED_EXIT_CODE
    path = dump_tmpl.replace("{rank}", "0")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "preemption_drain"
    assert doc["context"] == {"step": 6}
    steps = [r for r in doc["records"] if r.get("record") is None]
    assert len(steps) == 6

    fleet_report = _fleet_report()
    assert fleet_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "fleet heatmap" in out
    assert "6 step, 3 fleet view, 0 anomaly" in out
    chrome_out = str(tmp_path / "tl.json")
    assert fleet_report.main([path, "--format", "chrome",
                              "--out", chrome_out]) == 0
    with open(chrome_out) as f:
        tl = json.load(f)
    assert sum(1 for e in tl["traceEvents"] if e["ph"] == "X") == 6
    names = {e["args"]["name"] for e in tl["traceEvents"]
             if e["ph"] == "M"}
    assert names == {"rank 0"}


# --- dp2 CPU-mesh chaos lane: straggler named, dump survives SIGKILL --------

def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run(cmd, env, timeout=420):
    proc = subprocess.Popen(cmd, env=env, start_new_session=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        log, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        raise
    return proc.returncode, log


@pytest.mark.skipif(sys.platform != "linux", reason="loopback group")
def test_chaos_fleet_names_straggler_and_dump_survives_sigkill(tmp_path):
    d = str(tmp_path)
    env = dict(os.environ)
    env.update(REPO_ROOT=REPO, CKPT_DIR=d + "/ck", TOTAL_STEPS="36",
               OUT_FILE=d + "/out_", STEP_SLEEP="0",
               MXT_LAUNCH_PLATFORM="cpu",
               FLEET_JSONL=d + "/fleet.rank", FLEET_STRIDE="4",
               SLOW_RANK="1", SLOW_SLEEP="0.08",
               MXNET_FLEET_WINDOWS="2")
    dump_tmpl = d + "/fd.rank{rank}.json"
    summary_file = d + "/chaos.json"
    rc, log = _run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "-n", "2", "--kills", "1", "--mix", "kill", "--seed", "5",
         "--min-delay", "1.0", "--max-delay", "2.5",
         "--max-restarts", "6", "--backoff-base", "0.1",
         "--coordinator", f"127.0.0.1:{_free_port()}",
         "--summary", summary_file, "--fleet-dump", dump_tmpl,
         "--", sys.executable, WORKER], env)
    assert rc == 0, log[-3000:]
    with open(summary_file) as f:
        summary = json.load(f)
    assert summary["survived"]
    assert summary["injections"], summary
    assert all(i["signal"] == "SIGKILL" for i in summary["injections"])
    # a flight dump exists and is readable for every killed rank...
    assert summary["fleet_dumps_complete"], summary
    for _rank, path in summary["fleet_dumps"].items():
        with open(path) as f:
            doc = json.load(f)
        assert doc["record"] == "flight_recorder"
        assert doc["kind"] == "fleet"
        # ...embedding that rank's last >= 16 step records
        steps = [r for r in doc["records"] if r.get("record") is None
                 and "step_ms" in r]
        assert len(steps) >= 16, len(steps)

    # the merged per-rank streams name rank 1 as the straggler
    merged = read_jsonl(d + "/fleet.rank*.jsonl")
    views = [r for r in merged if r.get("record") == "fleet"]
    assert views
    flagged = [v for v in views if 1 in v.get("stragglers", [])]
    assert flagged, [v.get("stragglers") for v in views]
    anomalies = [r for r in merged if r.get("record") == "anomaly"
                 and r.get("kind") == "straggler"]
    assert anomalies
    assert all(a["culprit"] == 1 for a in anomalies), anomalies

    # ...and so does the report CLI, text and Perfetto both
    fleet_report = _fleet_report()
    rep = d + "/report.txt"
    assert fleet_report.main([d + "/fleet.rank*.jsonl",
                              "--out", rep]) == 0
    text = open(rep).read()
    straggler_line = next(ln for ln in text.splitlines()
                          if ln.startswith("stragglers"))
    assert "rank 1 (" in straggler_line, text
    tl_path = d + "/timeline.json"
    assert fleet_report.main([d + "/fleet.rank*.jsonl", "--format",
                              "chrome", "--out", tl_path]) == 0
    with open(tl_path) as f:
        tl = json.load(f)
    tracks = {e["args"]["name"] for e in tl["traceEvents"]
              if e["ph"] == "M"}
    assert {"rank 0", "rank 1"} <= tracks
    assert any(e["ph"] == "i" and e["name"] == "anomaly:straggler"
               for e in tl["traceEvents"])

"""Capacity observability (r20): duty-cycle ledgers, λ/μ/ρ headroom
estimators, the saturation watch, and the serving integration.

Four layers of proof:

* **Pure units** — the EWMA / rate-estimator / interval-ledger pieces
  and the queue-theory functions (``service_rate`` via the operational
  utilization law, ``queue_metrics``, ``duty_cycle``) driven with
  synthetic clocks: no serving stack, no real time.
* **Watch semantics** — the saturation watch is edge-triggered with
  hysteresis: one event per crossing, re-armed only after ρ falls
  below threshold × 0.8, gated on a minimum completion count.
* **Cost contract** — disabled, every hook is one module-global
  boolean: a poisoned lock proves nothing is acquired, and 10k no-op
  hook calls stay under the same bound the other telemetry tiers hold.
* **Serving end-to-end** — on a dp2 CPU-mesh generative server, an
  injected burst drives ρ past threshold and the ``saturation`` JSONL
  record lands in the stream BEFORE the first queue-wait breach (the
  leading-indicator claim), the r12 flight recorder dumps with
  ``reason="saturation"``, ``/healthz`` reports the degraded-but-alive
  ``saturated`` status at HTTP 200, and the scrape carries the
  utilization/ρ/headroom gauge families.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import serving, telemetry
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import capacity, tracing
from mxnet_tpu.telemetry.sinks import ListSink


def _capacity_off():
    capacity.disable()
    capacity.reset()


# --- pure units: estimators --------------------------------------------------

def test_ewma_first_sample_seeds():
    e = capacity.EWMA(alpha=0.5)
    assert e.value is None
    assert e.update(10.0) == 10.0
    assert e.update(0.0) == 5.0
    assert e.update(5.0) == 5.0


def test_rate_estimator_steady_stream():
    r = capacity.RateEstimator(alpha=0.2)
    assert r.rate is None                  # one event is not a rate
    for i in range(20):
        r.observe(i * 0.1)                 # 10 events/sec
    assert r.count == 20
    assert r.rate == pytest.approx(10.0, rel=1e-6)
    # rate_at inside the smoothed gap: unchanged
    assert r.rate_at(1.95) == pytest.approx(10.0, rel=1e-6)


def test_rate_estimator_open_gap_decays():
    r = capacity.RateEstimator(alpha=0.2)
    for i in range(20):
        r.observe(i * 0.1)
    # a 2 s silence after a 0.1 s cadence: the open gap bounds the
    # estimate down — a stopped stream must read as a falling rate
    decayed = r.rate_at(1.9 + 2.0)
    assert decayed < 10.0 / 2
    # and longer silence decays further (monotone in the open gap)
    assert r.rate_at(1.9 + 8.0) < decayed


def test_event_window_rate_same_timescale_as_utilization():
    w = capacity.EventWindow(window_s=10.0)
    assert w.rate(5.0) is None
    for i in range(100):
        w.observe(1000.0 + i * 0.01)       # 100/s for 1 s
    # ramp-up span: a 1 s-old stream reports its 1 s truth
    assert w.rate(1001.0) == pytest.approx(100.0, rel=0.02)
    # 4 s later the same 100 events dilute over the 5 s observed span
    assert w.rate(1005.0) == pytest.approx(20.0, rel=0.02)
    # gone quiet: zero, not a frozen estimate
    assert w.rate(1020.0) == 0.0
    assert w.count == 100


def test_interval_ledger_window_and_rampup():
    led = capacity.IntervalLedger(window_s=10.0)
    assert led.utilization(100.0) == 0.0   # empty: no divide-by-zero
    # 1 s-old ledger, 0.5 s busy: ramp-up denominator reports 50%,
    # not 5% of an empty 10 s window
    led.add(100.0, 100.5)
    assert led.utilization(101.0) == pytest.approx(0.5)
    # intervals behind the window stop counting
    assert led.utilization(120.0) == pytest.approx(0.0, abs=1e-9)
    # clamp: overlapping double-adds cannot exceed 1.0
    led.add(200.0, 201.0)
    led.add(200.0, 201.0)
    assert led.utilization(201.0) <= 1.0


def test_interval_ledger_ignores_empty_intervals():
    led = capacity.IntervalLedger(window_s=10.0)
    led.add(5.0, 5.0)
    led.add(6.0, 4.0)
    assert led.utilization(10.0) == 0.0


# --- pure units: queue theory ------------------------------------------------

def test_service_rate_utilization_law():
    # X = 50/s at 50% busy -> the replica would do 100/s flat out
    assert capacity.service_rate(50.0, 0.5) == pytest.approx(100.0)
    # fully busy: mu == X
    assert capacity.service_rate(80.0, 1.0) == pytest.approx(80.0)
    # below the busy floor the denominator is noise, not a divisor
    assert capacity.service_rate(50.0, 0.001) is None
    assert capacity.service_rate(None, 0.5) is None
    assert capacity.service_rate(0.0, 0.5) is None


def test_queue_metrics_rho_and_headroom():
    rho, headroom = capacity.queue_metrics(50.0, 100.0)
    assert rho == pytest.approx(0.5)
    assert headroom == pytest.approx(50.0)
    # overload clamps headroom at zero, rho goes past 1
    rho, headroom = capacity.queue_metrics(120.0, 100.0)
    assert rho == pytest.approx(1.2) and headroom == 0.0
    assert capacity.queue_metrics(None, 100.0) == (None, None)
    assert capacity.queue_metrics(50.0, 0.0) == (None, None)


def test_duty_cycle_clamps_and_survives_garbage():
    assert capacity.duty_cycle(8.0, 10.0) == pytest.approx(0.8)
    assert capacity.duty_cycle(12.0, 10.0) == 1.0
    assert capacity.duty_cycle(-1.0, 10.0) == 0.0
    assert capacity.duty_cycle(5.0, 0.0) == 0.0
    assert capacity.duty_cycle(None, None) == 0.0
    assert capacity.duty_cycle("x", "y") == 0.0


# --- watch semantics (synthetic clock) ---------------------------------------

def _drive_steady(index, t0, n=100, period=0.01, busy=0.5):
    """n arrivals/completions at 1/period rps with the decode lane
    busy the given fraction of each period."""
    for i in range(n):
        now = t0 + i * period
        capacity.note_arrival(index, t=now)
        capacity.note_completion(index, t=now + period * 0.4)
        capacity.note_tick(index, 4, 8, now, now + period * busy)


def test_saturation_fires_once_and_rearms(monkeypatch):
    capacity.enable(rho_threshold=0.85, min_completions=8)
    fired = []
    monkeypatch.setattr(capacity, "_emit_saturation", fired.append)
    try:
        _drive_steady(0, 1000.0)           # rho ~= 0.5: no event
        assert fired == []
        assert capacity.saturated() is False
        # burst: 400 rps arrivals against ~200 rps mu
        t = 1001.0
        for i in range(200):
            capacity.note_arrival(0, t=t + i * 0.0025)
            if i % 2 == 0:
                now = t + i * 0.0025
                capacity.note_completion(0, t=now + 0.004)
                capacity.note_tick(0, 8, 8, now, now + 0.0049)
        assert len(fired) == 1             # edge-triggered: ONE event
        evt = fired[0]
        assert evt["record"] == "saturation"
        assert evt["rho"] >= 0.85
        assert evt["replica"] == 0
        assert evt["headroom_rps"] == 0.0 or evt["headroom_rps"] >= 0
        assert capacity.saturated(0) is True
        # drain: rate falls far below threshold * 0.8 -> re-arms
        _drive_steady(0, 1002.0, n=300, period=0.05, busy=0.1)
        assert capacity.saturated(0) is False
        # second crossing fires a second event
        t = 1020.0
        for i in range(200):
            capacity.note_arrival(0, t=t + i * 0.0025)
            if i % 2 == 0:
                now = t + i * 0.0025
                capacity.note_completion(0, t=now + 0.004)
                capacity.note_tick(0, 8, 8, now, now + 0.0049)
        assert len(fired) == 2
    finally:
        _capacity_off()


def test_saturation_gated_on_min_completions(monkeypatch):
    capacity.enable(rho_threshold=0.5, min_completions=50)
    fired = []
    monkeypatch.setattr(capacity, "_emit_saturation", fired.append)
    try:
        _drive_steady(0, 1000.0, n=40, busy=0.9)   # rho ~0.9 > 0.5 ...
        assert fired == []                 # ... but only 40 completions
    finally:
        _capacity_off()


def test_snapshot_view_fields():
    capacity.enable()
    try:
        _drive_steady(3, 1000.0, n=200)
        capacity.note_kv(3, 60, 100, fragmentation=0.25)
        capacity.note_kv(3, 50, 100, fragmentation=0.35)
        capacity.note_spec(3, 40, 25)
        snap = capacity.snapshot(3, now=1001.99)
        assert snap["replica"] == 3
        assert 0.3 < snap["utilization"] < 0.7
        assert snap["occupancy"] == pytest.approx(0.5)
        assert snap["slot_capacity"] == 8
        assert snap["spec_efficiency"] == pytest.approx(25 / 40)
        assert snap["kv_free_frac"] == pytest.approx(0.5)
        assert snap["kv_fragmentation_trend"] > 0   # fragmenting
        assert snap["arrival_rate_rps"] == pytest.approx(100.0, rel=0.05)
        assert snap["rho"] == pytest.approx(0.5, rel=0.15)
        assert snap["predicted_max_rate_rps"] == \
            snap["service_rate_rps"]
        assert snap["headroom_rps"] > 0
        # the all-replica form keys by index
        assert set(capacity.snapshot(now=1001.99)) == {3}
        # utilization query matches the view
        assert capacity.utilization(3, now=1001.99) == \
            pytest.approx(snap["utilization"], abs=1e-6)
    finally:
        _capacity_off()


def test_telemetry_enable_kwarg_arms_capacity():
    try:
        telemetry.enable(memory=False, cost=False, capacity=True)
        assert capacity.is_enabled()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert not capacity.is_enabled()


# --- cost contract: the disabled path ----------------------------------------

class _PoisonLock:
    def __enter__(self):
        raise AssertionError("disabled capacity hook acquired a lock")

    def __exit__(self, *a):
        return False


def test_disabled_hooks_never_lock_or_record(monkeypatch):
    _capacity_off()
    monkeypatch.setattr(capacity, "_lock", _PoisonLock())
    capacity.note_arrival(0)
    capacity.note_completion(0, t=1.0)
    capacity.note_tick(0, 4, 8, 0.0, 1.0)
    capacity.note_spec(0, 4, 2)
    capacity.note_kv(0, 5, 10)
    capacity.lane_busy(0, "prefill", 0.0, 1.0)
    assert capacity.utilization(0) == 0.0
    assert capacity.saturated() is False
    assert capacity.snapshot(0) is None
    assert capacity.snapshot() == {}


def test_disabled_overhead_bounded():
    _capacity_off()
    t0 = time.perf_counter()
    for i in range(10_000):
        capacity.note_arrival(0, t=float(i))
        capacity.note_completion(0, t=float(i))
        capacity.note_tick(0, 4, 8, float(i), float(i) + 0.5)
        capacity.lane_busy(0, "prefill", float(i), float(i) + 0.1)
    dt = time.perf_counter() - t0
    # 40k disabled hook crossings; the bound matches the other tiers'
    # disabled-path guards (one boolean test per call)
    assert dt < 0.5, f"disabled capacity hooks cost {dt:.3f}s per 40k"


# --- serving end-to-end: burst -> saturation precedes the wait breach --------

def _tiny():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


def test_dp2_burst_saturation_precedes_queue_wait_breach(
        tmp_path, monkeypatch):
    """The leading-indicator claim, end to end: under an injected
    burst on a dp2 CPU-mesh server the ``saturation`` record enters
    the JSONL stream BEFORE any request record whose queue wait
    breached, the flight recorder dumps with ``reason="saturation"``,
    ``/healthz`` stays HTTP 200 with status ``saturated``, and the
    scrape exposes the capacity gauge families."""
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices (dp2)")
    breach_ms = 50.0
    dump_path = tmp_path / "flight.json"
    monkeypatch.setenv("MXNET_TRACE_DUMP", str(dump_path))
    net = _tiny()
    rs = np.random.RandomState(7)
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, summary_every=1 << 30,
                       http_port=0)
    telemetry.enable(memory=False, cost=False, trace=True)
    sink = ListSink()
    telemetry.add_sink(sink)
    capacity.enable(rho_threshold=0.85, min_completions=6)
    try:
        srv = serving.GenerativeServer(net, cfg, mesh=mesh)
        with srv:
            url = srv.metrics_url
            # warm trickle: enough completions per replica to trust mu,
            # spaced so the duty cycle stays well under the threshold.
            # The gap follows what the request took: a fixed 10 ms held
            # the duty cycle down only while a request cost a few ms,
            # and on a machine that six test workers share it costs
            # tens, rho passes 0.85 here and the watch latches early
            for _ in range(14):
                t_gen = time.perf_counter()
                srv.generate(rs.randint(1, 250, size=6),
                             max_new_tokens=3)
                took = time.perf_counter() - t_gen
                time.sleep(min(1.0, max(0.01, 4 * took)))
            assert capacity.saturated() is False
            # burst: far more than 2 replicas x 2 slots can drain
            futs = [srv.submit(rs.randint(1, 250, size=6),
                               max_new_tokens=6) for _ in range(24)]
            for f in futs:
                f.result(300)
            # the watch re-arms as the drain pulls rho back down, so
            # health is checked with the flag deterministically held:
            # a live crossing is timing, the PLUMBING is the claim here
            with capacity._lock:
                capacity._replica(0).saturated = True
            health = json.loads(
                urllib.request.urlopen(url + "/healthz").read())
            code = urllib.request.urlopen(url + "/healthz").status
            mtxt = urllib.request.urlopen(url + "/metrics").read() \
                .decode()
            stats = srv.stats()
            counters = dict(telemetry.counters())
    finally:
        telemetry.disable()
        telemetry.reset()
        tracing.clear()
        _capacity_off()

    # -- the stream ordering: saturation precedes the wait breach ------------
    sat_idx = [i for i, r in enumerate(sink.records)
               if r.get("record") == "saturation"]
    assert sat_idx, "no saturation record under a 24-deep burst"
    breach_idx = [i for i, r in enumerate(sink.records)
                  if r.get("record") == "serving.request"
                  and (r.get("queue_wait_ms") or 0.0) > breach_ms]
    assert breach_idx, "burst produced no queue-wait breach to lead"
    assert sat_idx[0] < breach_idx[0], (
        "saturation must be a LEADING indicator: record index %d vs "
        "first breach at %d" % (sat_idx[0], breach_idx[0]))
    sat = sink.records[sat_idx[0]]
    assert sat["rho"] >= 0.85
    assert sat["replica"] in (0, 1)
    assert sat["service_rate_rps"] > 0
    assert counters.get("capacity.saturation", 0) >= 1

    # -- the flight recorder armed on the crossing ---------------------------
    assert dump_path.exists()
    report = json.loads(dump_path.read_text())
    assert report["record"] == "flight_recorder"
    assert report["reason"] == "saturation"
    assert report["context"]["rho"] >= 0.85

    # -- degraded-but-alive health + gauges ----------------------------------
    assert code == 200
    assert health["status"] == "saturated"
    sat_reps = [r for r in health["replicas"] if r.get("saturated")]
    assert sat_reps and all("rho" in r and "headroom_rps" in r
                            for r in sat_reps)
    assert "mxt_serving_utilization" in mtxt
    assert "mxt_serving_rho" in mtxt
    assert "mxt_serving_headroom_rps" in mtxt
    assert "mxt_serving_kv_free_frac" in mtxt

    # -- stats carries the per-replica capacity views ------------------------
    caps = stats["capacity"]
    assert len(caps) == 2
    assert {c["replica"] for c in caps} == {0, 1}
    assert sum(c["saturation_events"] for c in caps) >= 1

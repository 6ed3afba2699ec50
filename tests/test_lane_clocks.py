"""The host's side of a turn on two clocks (``telemetry.tracing``): the ends of
a serving lane's host part on the wall's clock and the lane thread's own, the
collector's pauses as lane records and ``mxt.gc.pause`` spans, and the one rule
that names a stall and its cause.

A tiny llama server on the CPU writes the records; the rule is read off PLANTED
records whose stamps the test writes, so nothing here depends on how loaded the
machine is: a stamp is only ever compared with another stamp.
"""
import gc
import glob
import itertools
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

TICK = ("loop", "lock", "disp0", "disp1", "tok", "book")
BATCH = ("start", "disp1", "ready", "lock", "commit1", "first")
# the stamps that are taken on the thread's CPU clock too: the ends of the
# host part and of the wait for the device inside it, which ``tracing.stalls``
# and the benchmark's readers take differences of, and no other
TICK_CPU = ("loop", "disp1", "tok")
BATCH_CPU = ("start", "disp1", "ready", "first")


def _cpu_tick():
    """What one reading of the thread's CPU clock may add to a difference of
    two: the smallest step the clock is seen to take (``get_clock_info``
    promises a nanosecond on hosts that count in ticks of 10 ms)."""
    steps, c0 = [], time.thread_time()
    end = time.perf_counter() + 0.05
    while time.perf_counter() < end and len(steps) < 20:
        c1 = time.thread_time()
        if c1 != c0:
            steps.append(c1 - c0)
            c0 = c1
    return max(min(steps, default=0.05), 1e-4)


EPS = _cpu_tick()
# each planted stretch in a thousand seconds of its own, before any real
# ``perf_counter`` (no ``since=`` of another test sees it) and apart from
# test_lane_log's planted records
_bases = itertools.count(-300_000_000, 1000)


def _tiny():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


def _serve(**cfg):
    """Five requests through a tiny server -> the lane records it wrote."""
    kw = dict(max_batch=2, max_length=64, min_length=8, num_slots=2)
    kw.update(cfg)
    srv = serving.GenerativeServer(_tiny(), ServerConfig(**kw))
    rs = np.random.RandomState(0)
    since = time.perf_counter()
    with srv:
        for f in [srv.submit(rs.randint(1, 250, size=6), max_new_tokens=5)
                  for _ in range(5)]:
            f.result(120)
    return tracing.lane_log(since=since)


@pytest.fixture(scope="module")
def served():
    return _serve()


@pytest.fixture(scope="module")
def speculating():
    """The serial tick: the record's stamps are the step's own."""
    net = _tiny()
    return _serve(draft_net=net, spec_k=2)


def _check_clocks(records, stamps, on_cpu):
    assert records
    for rec in records:
        wall = [rec[f"t_{s}"] for s in stamps]
        assert wall == sorted(wall)
        # a stamp that nothing reads is not taken
        assert {k for k in rec if k[:2] in ("c_", "p_")} == {
            f"c_{s}" for s in on_cpu}
        cpu = [rec[f"c_{s}"] for s in on_cpu]
        assert None not in cpu and cpu == sorted(cpu)
        # a thread cannot have computed for longer than the time that passed
        at = [rec[f"t_{s}"] for s in on_cpu]
        for (t0, t1), (c0, c1) in zip(zip(at, at[1:]), zip(cpu, cpu[1:])):
            assert c1 - c0 <= t1 - t0 + EPS
    for a, b in zip(records, records[1:]):    # one thread, one order
        assert a[f"c_{on_cpu[-1]}"] <= b[f"c_{on_cpu[0]}"]


@pytest.mark.parametrize("kind, stamps, on_cpu", [
    ("decode.tick", TICK, TICK_CPU),
    ("prefill.batch", BATCH, BATCH_CPU)])
def test_every_record_carries_its_host_parts_ends_on_two_clocks(
        served, kind, stamps, on_cpu):
    _check_clocks([r for r in served if r["kind"] == kind], stamps, on_cpu)


def test_a_serial_ticks_record_carries_the_steps_own_clocks(speculating):
    ticks = [r for r in speculating if r["kind"] == "decode.tick"]
    _check_clocks(ticks, TICK, TICK_CPU)
    assert all("accepted" in t for t in ticks)


def test_a_lane_that_waited_says_for_how_long(served):
    """The lane stood empty before the first request and not between two
    ticks of a request: ``idle_s`` is the wait since the last record."""
    ticks = [r for r in served if r["kind"] == "decode.tick"]
    assert all(0.0 <= t["idle_cpu_s"] <= t["idle_s"] + EPS for t in ticks)
    for a, b in zip(ticks, ticks[1:]):
        assert b["idle_s"] <= b["t_loop"] - a["t_book"] + EPS


def test_a_wait_of_many_polls_is_one_stretch_to_the_next_turns_top():
    """What lies between two polls is part of the wait: over seconds of
    polling those slivers would add up to a stall nobody had."""
    import types

    from mxnet_tpu.serving.lanes import DecodeLane

    lane = DecodeLane(types.SimpleNamespace(index=0), poll_s=1e-4)
    lane._rest()
    t0, c0 = lane._idle_from
    for _ in range(20):
        lane._rest()
    assert lane._idle_from == (t0, c0) and lane._idle_s == 0.0
    lane._adopt()
    assert lane._idle_from is None
    assert lane._idle_s == lane._t_loop - t0 > 20 * 1e-4
    assert lane._idle_cpu_s == lane._c_loop - c0


# --- the collector ---------------------------------------------------------

def test_a_forced_collection_over_a_planted_heap_is_one_pause_record():
    heap = [[i] for i in range(400_000)]      # what a full collection walks
    cycles = []                                # and what it frees
    for _ in range(1000):
        cycle = []
        cycle.append(cycle)
        cycles.append(cycle)    # held until every one of them is made
    del cycle, cycles
    before = tracing.gc_stats()
    since = time.perf_counter()
    gc.collect()
    until = time.perf_counter()
    after = tracing.gc_stats()
    pauses = [p for p in tracing.lane_log("gc.pause", since=since)
              if p["t0"] < until]
    assert len(pauses) == 1 and len(heap) == 400_000
    (pause,) = pauses
    assert pause["generation"] == 2 and pause["collected"] >= 1000
    assert pause["thread"] == threading.current_thread().name
    assert since <= pause["t0"] < pause["t1"] <= until
    assert pause["t1"] - pause["t0"] >= tracing.GC_PAUSE_MIN_S
    assert after["runs"] == before["runs"] + 1
    assert after["seconds"] - before["seconds"] == pytest.approx(
        pause["t1"] - pause["t0"])


def test_a_short_collection_writes_no_record_and_still_counts(monkeypatch):
    assert tracing.GC_PAUSE_MIN_S == 1e-3
    # "short" by the constant, not by this machine's load
    monkeypatch.setattr(tracing, "GC_PAUSE_MIN_S", 3600.0)
    before = tracing.gc_stats()
    since = time.perf_counter()
    gc.collect()
    after = tracing.gc_stats()
    assert tracing.lane_log("gc.pause", since=since) == []
    assert after["runs"] == before["runs"] + 1
    assert after["seconds"] > before["seconds"]


def test_a_collection_lies_under_an_mxt_span_in_the_xplane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name == "mxt.gc.pause"]
    assert {"generation": 2} in spans


# --- the rule that names a stall --------------------------------------------

# a planted turn, milliseconds a phase: a period of 18, 3 of them the host's, of
# which the lane thread computes 2.4
PHASES = dict(adopt=0.2, lock=0.1, dispatch=2.0, fetch=15.0, book=0.5, tail=0.2)
LANE_CPU = 0.8          # of a host phase's wall time


def plant_turns(base, n=12, clocks=True, **slow):
    """``n`` + 1 tick records from ``base`` on (so ``n`` turns), turn 5 slowed:
    ``slow`` gives ``phase`` and ``wall`` (extra ms in it), ``lane`` (extra
    CPU ms of the lane thread), ``idle`` (ms the lane waited in the tail) ->
    the stretch ``(since, until)``."""
    t, c = base, 5.0
    for seq in range(1, n + 2):
        rec = dict(replica=0, seq=seq, n_active=2, n_adopted=0, n_finished=0,
                   request_ids=(1, 2), idle_s=0.0)
        for stamp, phase in zip(TICK, PHASES):
            rec[f"t_{stamp}"] = t
            if clocks and stamp in TICK_CPU:
                rec[f"c_{stamp}"] = c
            wall = PHASES[phase] * 1e-3
            lane = 0.0 if phase == "fetch" else LANE_CPU * wall
            if seq == 5 and slow.get("phase") == phase:
                wall += slow["wall"] * 1e-3
                lane += slow.get("lane", 0.0) * 1e-3
            t, c = t + wall, c + lane
        if seq == 6:      # the wait lies in turn 5's tail, told by the next
            rec["idle_s"] = slow.get("idle", 0.0) * 1e-3
        tracing.lane_record("decode.tick", **rec)
    return base, t


@pytest.fixture
def base():
    """Where a test plants its records, which are taken out of the ring again:
    other files' tests read it whole."""
    at = float(next(_bases))
    yield at
    kept = [r for r in tracing.lane_log()
            if not at <= r[tracing._LANE_SPAN[r["kind"]][0]] < at + 1000]
    tracing._lane_log.clear()
    tracing._lane_log.extend(kept)


def _one(found, **want):
    (stall,) = found
    assert {k: stall[k] for k in want} == want
    return stall


def test_a_turn_under_the_collector_is_gc(base):
    since, until = plant_turns(base, phase="book", wall=60.0)
    book = base + 4 * 18e-3 + 17.3e-3        # turn 5's booking begins
    tracing.lane_record("gc.pause", t0=book + 0.005, t1=book + 0.055,
                        generation=2, collected=0, thread="mxt-prefill-r0")
    stall = _one(tracing.stalls(since, until), lane="decode", replica=0, seq=5,
                 phase="book", cause="gc")
    assert stall["wall_ms"] == pytest.approx(60.0)
    assert stall["t0"] == pytest.approx(book)


def test_a_pause_over_less_than_half_of_a_stall_does_not_name_it(base):
    since, until = plant_turns(base, phase="book", wall=60.0)
    book = base + 4 * 18e-3 + 17.3e-3
    tracing.lane_record("gc.pause", t0=book, t1=book + 0.029, generation=2,
                        collected=0, thread="MainThread")
    _one(tracing.stalls(since, until), cause="offcpu")


def test_a_turn_the_lane_computed_through_is_own(base):
    since, until = plant_turns(base, phase="book", wall=40.0, lane=38.0)
    stall = _one(tracing.stalls(since, until), phase="book", cause="own")
    assert stall["wall_ms"] == pytest.approx(40.0)
    # over the stretch's mean, which holds a twelfth of them
    assert stall["cpu_ms"] == pytest.approx(38.0 * 11 / 12)


@pytest.mark.parametrize("phase, wall, lane, t0", [
    # the lane's thread ran nothing of it: the machine, or another thread
    ("tail", 80.0, 0.0, 17.8e-3),
    # a millisecond of it: under half, so not the lane's own
    ("dispatch", 30.0, 1.0, 0.3e-3)])
def test_a_turn_kept_off_the_cpu_with_no_collection_is_offcpu(
        base, phase, wall, lane, t0):
    since, until = plant_turns(base, phase=phase, wall=wall, lane=lane)
    stall = _one(tracing.stalls(since, until), phase=phase, cause="offcpu")
    assert stall["wall_ms"] == pytest.approx(wall, abs=1e-3)
    assert stall["t0"] == pytest.approx(base + 4 * 18e-3 + t0)


@pytest.mark.parametrize("over", [9.0, 19.0])
def test_a_turn_under_the_constant_over_the_median_is_left_alone(base, over):
    assert tracing.STALL_MIN_S == 0.020
    since, until = plant_turns(base, phase="book", wall=over)
    assert tracing.stalls(since, until) == []
    since, until = plant_turns(until + 1.0, phase="book", wall=21.0)
    _one(tracing.stalls(since, until), phase="book")


def test_the_wait_for_the_devices_tokens_is_no_stall(base):
    since, until = plant_turns(base, phase="fetch", wall=100.0)
    assert tracing.stalls(since, until) == []


def test_a_lane_that_waited_for_work_did_not_stall(base):
    since, until = plant_turns(base, phase="tail", wall=200.0, idle=195.0)
    assert tracing.stalls(since, until) == []


def test_records_without_the_cpu_clocks_give_nothing(base):
    since, until = plant_turns(base, clocks=False, phase="book", wall=60.0)
    assert tracing.stalls(since, until) == []


def test_under_five_turns_their_median_says_nothing(base):
    since, until = plant_turns(base, n=4, phase="book", wall=60.0)
    assert tracing.stalls(since, until) == []


def test_the_asked_stretch_bounds_the_turns_and_their_median(base):
    since, until = plant_turns(base, phase="book", wall=60.0)
    # from turn 6 on: the stalled turn began before the stretch
    assert tracing.stalls(base + 5 * 18e-3 + 0.06, until) == []
    assert tracing.stalls(since, base + 3 * 18e-3) == []     # three turns
    assert len(tracing.stalls(since, until)) == 1


def test_a_prefill_batch_stalls_by_the_same_rule(base):
    """Eight batches of 30 ms, 4 of them the host's; the sixth holds the
    device lock's wait 50 ms longer."""
    t, c = base, 2.0
    for seq in range(1, 9):
        rec = dict(replica=1, seq=seq, request_ids=(seq,), n_tokens=8,
                   bucket=(1, 8), radix_hit_tokens=0)
        walls = dict(start=2.0, disp1=26.0, ready=0.5, lock=1.0, commit1=0.5)
        if seq == 6:
            walls["lock"] += 50.0
        for stamp in BATCH:
            rec[f"t_{stamp}"] = t
            if stamp in BATCH_CPU:
                rec[f"c_{stamp}"] = c
            wall = walls.get(stamp, 0.0) * 1e-3
            # the lane computes through half of a host phase, and not at all
            # while it waits: for the device's tokens, for the lock
            lane = 0.0 if stamp == "disp1" else 0.5 * min(wall, 0.002)
            t, c = t + wall, c + lane
        tracing.lane_record("prefill.batch", **rec)
        t += 0.005
    stall = _one(tracing.stalls(base, t), lane="prefill", replica=1, seq=6,
                 phase="commit", cause="offcpu")
    assert stall["wall_ms"] == pytest.approx(50.0)


def test_totals_are_count_longest_and_milliseconds_by_cause(base):
    since, until = plant_turns(base, phase="tail", wall=80.0)
    totals = tracing.stall_totals(tracing.stalls(since, until))
    assert totals["count"] == 1
    assert totals["longest_ms"] == pytest.approx(80.0)
    assert set(totals["ms_by_cause"]) == set(tracing.STALL_CAUSES)
    assert totals["ms_by_cause"]["offcpu"] == pytest.approx(80.0)
    assert sum(totals["ms_by_cause"].values()) == pytest.approx(80.0)


def test_an_idle_servers_stats_carry_the_stalls_keys():
    srv = serving.GenerativeServer(_tiny(), ServerConfig(
        max_batch=2, max_length=64, min_length=8, num_slots=2))
    with srv:
        lanes = srv.stats()["lanes"]
    assert len(lanes) == 1
    stalls = lanes[0]["stalls"]
    assert set(stalls) == {"count", "longest_ms", "ms_by_cause"}
    assert set(stalls["ms_by_cause"]) == {"gc", "own", "offcpu"}
    assert stalls["count"] >= 0 and stalls["longest_ms"] >= 0.0

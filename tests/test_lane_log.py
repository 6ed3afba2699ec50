"""The lane log (``telemetry.tracing``): always-on tick, prefill-batch,
slot-turn and train-dispatch records, the prefill lane's busy / gated / idle
clock, what each lane's record says of the other lane's work on the device,
the ``mxt.*`` spans on the profiler's clock (a lane thread always under one),
and the names the benchmark's readers hold the program to.

Everything runs a tiny llama server or a tiny trainer on the CPU; a stamp is
only ever compared with another stamp, never read as a time.
"""
import glob
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, serving, telemetry
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")

TICK_STAMPS = ("t_loop", "t_lock", "t_disp0", "t_disp1", "t_tok", "t_book")
BATCH_STAMPS = ("t_start", "t_disp1", "t_ready", "t_lock", "t_commit1",
                "t_first")
TURN_STAMPS = ("t_start", "t_first", "t_handoff", "t_adopt", "t_tok")
# the spans a lane thread (mxt-prefill-r0, mxt-decode-r0) is always under
TOP_LEVEL = (("mxt.prefill.batch", "mxt.prefill.wait"),
             ("mxt.decode.tick", "mxt.decode.wait"))


def _tiny():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


def _server(make=None, **cfg):
    """A tiny server: two slots and prefill batches of two, but for ``cfg``."""
    kw = dict(max_batch=2, max_length=64, min_length=8, num_slots=2)
    kw.update(cfg)
    return serving.GenerativeServer((make or _tiny)(), ServerConfig(**kw))


def _serve(n_requests=5, new_tokens=5, make=None, **cfg):
    """Run ``n_requests`` through a tiny server -> (requests, the lane
    records it wrote, its stats, the engine)."""
    srv = _server(make, **cfg)
    rs = np.random.RandomState(0)
    since = time.perf_counter()
    with srv:
        futs = [srv.submit(rs.randint(1, 250, size=6),
                           max_new_tokens=new_tokens)
                for _ in range(n_requests)]
        for f in futs:
            f.result(120)
        stats = srv.stats()
    return ([f.request for f in futs], tracing.lane_log(since=since), stats,
            srv.engine)


@pytest.fixture(scope="module")
def served():
    assert not telemetry.is_enabled() and not tracing.is_enabled()
    return _serve()


@pytest.fixture(scope="module")
def one_slot():
    """Three requests queued for one slot, a prefill batch of one."""
    return _serve(n_requests=3, new_tokens=8, num_slots=1, max_batch=1)


@pytest.fixture
def no_collection():
    """A collection of a millisecond writes a ``gc.pause`` record of its
    own into the ring; a test that counts the ring's records holds the
    collector off."""
    import gc

    gc.disable()
    yield
    gc.enable()


def _kind(log, kind):
    return [r for r in log if r["kind"] == kind]


def _bench_module(*parts):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    path = os.path.join(BENCH, *parts)
    name = "lane_log_test_" + parts[-1].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- decode ticks ------------------------------------------------------------

def test_log_fills_with_telemetry_and_tracing_disabled(served):
    reqs, log, _stats, _eng = served
    assert {r["kind"] for r in log} >= {"decode.tick", "prefill.batch"}
    assert all(q.trace is None for q in reqs)     # the span trees stayed off
    assert tracing.recent() == [] or all(
        t["request_id"] not in {q.id for q in reqs} for t in tracing.recent())


def test_tick_seq_is_engine_steps_and_monotone(served):
    _reqs, log, stats, eng = served
    ticks = _kind(log, "decode.tick")
    assert [t["seq"] for t in ticks] == list(range(1, eng.steps + 1))
    assert stats["decode_steps"] == eng.steps == ticks[-1]["seq"]


@pytest.mark.parametrize("stamp", TICK_STAMPS)
def test_tick_stamps_monotone_across_ticks(served, stamp):
    ticks = _kind(served[1], "decode.tick")
    vals = [t[stamp] for t in ticks]
    assert vals == sorted(vals) and len(set(vals)) == len(vals)


def test_tick_stamps_ordered_within_a_tick_and_phases_sum_to_period(served):
    ticks = _kind(served[1], "decode.tick")
    for a, b in zip(ticks, ticks[1:]):
        edges = [a[s] for s in TICK_STAMPS] + [b["t_loop"]]
        assert edges == sorted(edges)
        phases = [e1 - e0 for e0, e1 in zip(edges, edges[1:])]
        assert sum(phases) == pytest.approx(b["t_loop"] - a["t_loop"],
                                            abs=1e-9)
    for t in ticks:
        assert t["n_active"] == len(t["request_ids"]) <= 2
        assert 0 <= t["n_finished"] <= t["n_active"]
    assert sum(t["n_adopted"] for t in ticks) == 5
    assert sum(t["n_finished"] for t in ticks) == 5


def test_request_tokens_are_the_ticks_first_tick_to_done_step(served):
    reqs, log, _stats, _eng = served
    ticks = {t["seq"]: t for t in _kind(log, "decode.tick")}
    batches = _kind(log, "prefill.batch")
    for q in reqs:
        mine = [ticks[s] for s in range(q.first_tick, q.done_step + 1)]
        # the first token is prefill's, every later one a tick's
        assert len(mine) == q.max_new_tokens - 1
        assert all(q.id in t["request_ids"] for t in mine)
        assert q.id not in ticks.get(q.first_tick - 1, {"request_ids": ()})[
            "request_ids"]
        batch = [b for b in batches if q.id in b["request_ids"]]
        assert len(batch) == 1 and batch[0]["t_first"] == q.t_first
        assert q.t_first <= mine[0]["t_disp0"]
        # finished inside the last tick's bookkeeping
        assert mine[-1]["t_tok"] <= q.t_done <= mine[-1]["t_book"]


def test_gap_means_reproduce_the_benchmarks_tpot(served):
    """The benchmark's ``itl_p99_ms`` pools per-token gaps; a request's mean
    of them is its ``tpot`` (``t_done`` lies in the last tick's
    bookkeeping, a few microseconds after its ``t_tok``)."""
    reqs, log, _stats, _eng = served
    lane_spans = _bench_module("lane_spans.py")
    t0 = min(r[tracing._LANE_SPAN[r["kind"]][0]] for r in log) - 1e-3
    obs = {"t0_abs": t0, "window_s": time.perf_counter() - t0}
    gaps = lane_spans.token_gaps(obs)
    for q in reqs:
        assert len(gaps[q.id]) == q.max_new_tokens - 1
        assert np.mean(gaps[q.id]) * 1e3 == pytest.approx(q.tpot_ms(),
                                                          abs=1.0)
    itl = _bench_module("layer_metrics", "itl_p99_ms.py").read(obs)
    assert itl >= max(np.mean(g) for g in gaps.values()) * 1e3


# --- the prefill lane --------------------------------------------------------

def test_prefill_batch_records(served):
    reqs, log, _stats, _eng = served
    batches = _kind(log, "prefill.batch")
    assert [b["seq"] for b in batches] == list(range(1, len(batches) + 1))
    assert sorted(i for b in batches for i in b["request_ids"]) \
        == sorted(q.id for q in reqs)
    for b in batches:
        assert [b[s] for s in BATCH_STAMPS] == sorted(b[s]
                                                      for s in BATCH_STAMPS)
        assert b["n_tokens"] == 6 * len(b["request_ids"])
        assert b["bucket"] == (len(b["request_ids"]), 8)
        assert b["radix_hit_tokens"] == 0 and b["replica"] == 0


def test_lane_clock_sums_to_wall_time(served):
    _reqs, log, stats, _eng = served
    (lane,) = stats["lanes"]
    parts = lane["busy_s"] + lane["gated_s"] + lane["idle_s"]
    assert parts == pytest.approx(lane["wall_s"], rel=0.05)
    assert lane["batches"] == len(_kind(log, "prefill.batch"))
    after = tracing.lane_state(0)        # the clock outlives the server
    assert after["batches"] == lane["batches"]
    assert after["busy_s"] == pytest.approx(lane["busy_s"])


def test_gated_rises_when_two_requests_queue_for_one_slot(one_slot):
    _reqs, log, stats, _eng = one_slot
    (lane,) = stats["lanes"]
    gated = _kind(log, "prefill.gated")
    assert lane["gates"].get("slot", 0) >= 2 and len(gated) >= 2
    assert all(g["reason"] == "slot" and g["t1"] > g["t0"] for g in gated)
    assert lane["gated_s"] == pytest.approx(
        sum(g["t1"] - g["t0"] for g in gated), rel=0.05)
    # a gated stretch ends where the next batch starts
    starts = {b["t_start"] for b in _kind(log, "prefill.batch")}
    assert all(g["t1"] in starts for g in gated)
    assert lane["busy_s"] + lane["gated_s"] + lane["idle_s"] \
        == pytest.approx(lane["wall_s"], rel=0.05)


# --- a slot's turn: release, batch, hand-off, first tick ----------------------

def _check_turns(reqs, log):
    """Every turn's stamps in order, on stamps that other records and the
    requests already hold, and its ids those of the records it names."""
    by_id = {q.id: q for q in reqs}
    batches = {b["seq"]: b for b in _kind(log, "prefill.batch")}
    ticks = {t["seq"]: t for t in _kind(log, "decode.tick")}
    turns = _kind(log, "slot.turn")
    for turn in turns:
        stamps = [turn[s] for s in TURN_STAMPS]
        assert stamps == sorted(stamps)
        assert turn["t_free"] is None or turn["t_free"] <= turn["t_start"]
        q, batch, tick = (by_id[turn["request_id"]], batches[turn["batch"]],
                          ticks[turn["tick"]])
        assert q.id in batch["request_ids"] and q.id in tick["request_ids"]
        assert (turn["t_start"], turn["t_first"]) \
            == (batch["t_start"], batch["t_first"]) == (q.t_start, q.t_commit)
        assert turn["t_adopt"] == q.t_handoff
        # adopted by the turn that queued the step (``t_step_*``), or by one
        # before it that queued none; that step's tokens came a turn later,
        # in the turn of ``t_loop``
        assert turn["t_adopt"] <= tick["t_step_lock"]
        assert turn["t_tok"] == tick["t_tok"]
        assert (turn["slot"], turn["replica"]) == (q.slot, 0)
        # the first tick the request was active in is the one that took it up
        assert q.first_tick == turn["tick"]
    return turns


def test_turn_stamps_in_order_and_its_ids_name_their_records(served):
    reqs, log, _stats, _eng = served
    turns = _check_turns(reqs, log)
    assert sorted(t["request_id"] for t in turns) == sorted(q.id for q in reqs)


def test_turns_of_a_window_are_its_ticks_adoptions(served):
    _reqs, log, _stats, _eng = served
    per_tick = {}
    for turn in _kind(log, "slot.turn"):
        per_tick[turn["tick"]] = per_tick.get(turn["tick"], 0) + 1
    ticks = _kind(log, "decode.tick")
    assert {t["seq"]: t["n_adopted"] for t in ticks if t["n_adopted"]} \
        == per_tick
    # a turn follows its tick's record, so a tail of the log holds both
    kinds = [r["kind"] for r in log if r["kind"] in ("decode.tick",
                                                     "slot.turn")]
    assert kinds[0] == "decode.tick"
    # any window of the log: the turns that end in it are its ticks'
    lo, hi = ticks[1]["t_loop"], ticks[-2]["t_loop"]
    inside = [t for t in ticks if lo <= t["t_tok"] < hi]
    assert sum(t["n_adopted"] for t in inside) == len(
        [t for t in tracing.lane_log("slot.turn", since=lo, until=hi)
         if lo <= t["t_tok"] < hi])


def test_ramp_turns_carry_no_release_later_ones_the_leaving_request(served):
    reqs, log, _stats, _eng = served
    by_id = {q.id: q for q in reqs}
    last = {}       # slot -> the turn before
    for turn in _kind(log, "slot.turn"):
        before = last.get(turn["slot"])
        if before is None:      # the ramp: the slot held nothing
            assert (turn["t_free"], turn["prev_request_id"],
                    turn["freed_by"]) == (None, None, None)
        else:
            left = by_id[before["request_id"]]
            assert (turn["t_free"], turn["prev_request_id"],
                    turn["freed_by"]) == (left.t_done, left.id, left.done_step)
        last[turn["slot"]] = turn
    assert len(last) == 2       # both slots turned, 5 requests over them


def test_a_request_done_at_prefill_writes_no_turn_and_stamps_its_release():
    srv = _server(max_batch=1, num_slots=1)
    rs = np.random.RandomState(2)
    since = time.perf_counter()
    with srv:
        one = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=1)
        one.result(120)
        more = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=3)
        more.result(120)
    turns = tracing.lane_log("slot.turn", since=since)
    assert [t["request_id"] for t in turns] == [more.request.id]
    (turn,) = turns
    # no tick had run when the first request left its slot
    assert (turn["t_free"], turn["prev_request_id"], turn["freed_by"]) \
        == (one.request.t_done, one.request.id, 0)
    assert one.request.t_handoff is None and one.request.first_tick is None
    _check_turns([more.request], tracing.lane_log(since=since))


def test_a_block_decoders_turn():
    """Its prefill yields no token: the hand-off carries none, the turn's
    ``t_first`` is the batch's (``t_commit``) and the request's own
    ``t_first`` comes later, from a tick."""
    reqs, log, _stats, _eng = _serve(n_requests=3, new_tokens=5,
                                     make=_tiny_block_decoder)
    turns = _check_turns(reqs, log)
    assert len(turns) == 3 == sum(t["n_adopted"]
                                  for t in _kind(log, "decode.tick"))
    for turn in turns:
        q = next(q for q in reqs if q.id == turn["request_id"])
        assert turn["t_first"] == q.t_commit < turn["t_tok"] <= q.t_first
    assert turns[2]["prev_request_id"] in {turns[0]["request_id"],
                                          turns[1]["request_id"]}


def test_a_failed_tick_releases_its_slots_and_leaves_no_step_in_flight():
    srv = _server(max_batch=1, num_slots=1)
    eng = srv.engine
    rs = np.random.RandomState(3)
    since = time.perf_counter()
    with srv:
        srv.generate(rs.randint(1, 250, size=6), max_new_tokens=2)
        step, eng._step = eng._step, None       # the next dispatch raises
        lost = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=4)
        with pytest.raises(TypeError):
            lost.result(120)
        eng._step = step
        assert eng.step_in_flight is None
        kept = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=3)
        kept.result(120)
    turns = tracing.lane_log("slot.turn", since=since)
    # the lost request was adopted by a turn that wrote no tick, so no turn
    assert lost.request.id not in {t["request_id"] for t in turns}
    turn = turns[-1]
    assert turn["request_id"] == kept.request.id
    assert (turn["t_free"], turn["prev_request_id"]) \
        == (lost.request.t_done, lost.request.id)
    assert sum(t["n_adopted"] for t in
               tracing.lane_log("decode.tick", since=since)) == len(turns)


def test_each_lanes_record_names_what_it_was_dispatched_behind(monkeypatch):
    """A step held in flight while a prefill's forward is dispatched, then
    that prefill held in flight over the next step's dispatch."""
    from mxnet_tpu.serving import generative, lanes

    hold_step, hold_prefill = threading.Event(), threading.Event()
    step_held, prefill_held = threading.Event(), threading.Event()
    lane_fetch, step_fetch = lanes._lane_materialize, generative._materialize

    def held_step_fetch(arrays):
        if hold_step.is_set():
            step_held.set()
            while hold_step.is_set():
                time.sleep(0.001)
        return step_fetch(arrays)

    def held_prefill_fetch(arrays):
        if hold_prefill.is_set():
            prefill_held.set()
            while hold_prefill.is_set():
                time.sleep(0.001)
        return lane_fetch(arrays)

    monkeypatch.setattr(generative, "_materialize", held_step_fetch)
    monkeypatch.setattr(lanes, "_lane_materialize", held_prefill_fetch)
    srv = _server(max_batch=1)
    rs = np.random.RandomState(4)
    since = time.perf_counter()
    try:
        with srv:
            first = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=12)
            while first.request.first_tick is None:
                time.sleep(0.001)
            hold_step.set()
            assert step_held.wait(60)
            held_seq = srv.engine.step_in_flight
            assert held_seq is not None
            hold_prefill.set()
            second = srv.submit(rs.randint(1, 250, size=6), max_new_tokens=3)
            assert prefill_held.wait(60)    # dispatched behind the held step
            (held_batch,) = srv.engine.prefill_in_flight
            hold_step.clear()
            for _ in range(60_000):          # a later tick goes out behind it
                if any(t["behind"] for t in
                       tracing.lane_log("decode.tick", since=since)):
                    break
                time.sleep(0.001)
            hold_prefill.clear()
            second.result(120)
            first.result(120)
    finally:
        hold_step.clear()
        hold_prefill.clear()
    assert srv.engine.prefill_in_flight == () \
        and srv.engine.step_in_flight is None
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert [b["behind_tick"] for b in batches] == [None, held_seq]
    assert batches[1]["seq"] == held_batch
    behind = [t for t in ticks if t["behind"]]
    assert behind and all(t["behind"] == (held_batch,) for t in behind)
    assert behind[0]["seq"] == held_seq + 1
    # the ticks queued behind it: after its forward, before its first tokens
    assert all(batches[1]["t_disp1"] <= t["t_step_disp1"]
               <= batches[1]["t_ready"] for t in behind)
    assert all(t["behind"] == () for t in ticks if t["seq"] <= held_seq)


def test_batch_records_carry_the_counts_at_the_gate(served, one_slot):
    _reqs, log, _stats, _eng = one_slot
    batches = _kind(log, "prefill.batch")
    assert [b["free_slots"] for b in batches] == [1, 1, 1]
    # the batch's own request is among the queued; the dispatcher may still
    # hold the others when the first batch is taken
    assert 1 <= batches[0]["queued"] <= 3
    assert [b["queued"] for b in batches[1:]] == [2, 1]
    two = _kind(served[1], "prefill.batch")
    assert two[0]["free_slots"] == 2 and all(
        1 <= b["free_slots"] <= 2 and b["queued"] >= len(b["request_ids"])
        for b in two)


# --- the ring ----------------------------------------------------------------

def test_ring_is_bounded_and_lane_log_filters(no_collection):
    base = -1e9     # before any real perf_counter: no ``since=`` sees them
    for i in range(tracing.LANE_LOG_CAPACITY + 50):
        tracing.lane_record("prefill.gated", replica=7, t0=base + i,
                            t1=base + i + 0.5, reason="slot")
    assert len(tracing.lane_log()) == tracing.LANE_LOG_CAPACITY
    mine = tracing.lane_log(kind="prefill.gated", until=0.0)
    assert len(mine) == tracing.LANE_LOG_CAPACITY
    assert mine[0]["t0"] == base + 50        # the oldest fell off the ring
    last = base + tracing.LANE_LOG_CAPACITY + 49
    # records that overlap [since, until): last stamp >= since, first < until
    got = tracing.lane_log(since=last - 2.25, until=last)
    assert [r["t0"] for r in got] == [last - 2, last - 1]
    assert tracing.lane_log(kind="decode.tick", until=0.0) == []
    assert tracing.lane_log(until=base) == []
    assert tracing.lane_log(since=0.0, until=1.0) == []


def test_lane_log_filters_turns_by_their_batch_start_and_tick():
    base = -2e9     # before any real perf_counter, apart from the ring test's
    for i in range(4):
        t = base + 10 * i
        tracing.lane_record(
            "slot.turn", replica=7, slot=0, request_id=i, batch=i + 1,
            tick=i + 1, freed_by=None, prev_request_id=None, t_free=None,
            t_start=t, t_first=t + 1, t_handoff=t + 1, t_adopt=t + 2,
            t_tok=t + 3)
    def got(**kw):
        return [r["request_id"] for r in tracing.lane_log("slot.turn", **kw)
                if r["replica"] == 7]

    assert got(until=base + 100) == [0, 1, 2, 3]
    # last stamp (t_tok) at or after since, first (t_start) before until
    assert got(since=base + 13, until=base + 30) == [1, 2]
    assert got(since=base + 13.5, until=base + 30.5) == [2, 3]
    assert got(since=base + 34, until=base + 100) == []
    assert tracing.lane_log("decode.tick", until=base + 100) == []


def test_lane_record_cost_is_bounded():
    t0 = time.perf_counter()
    for i in range(10_000):
        tracing.lane_record("prefill.gated", replica=7, t0=-1e9, t1=-1e9,
                            reason="slot")
    assert time.perf_counter() - t0 < 0.5


# --- the surface -------------------------------------------------------------

def test_submit_future_carries_the_request(served):
    reqs, _log, _stats, _eng = served
    for q in reqs:
        assert q.future.request is q
        assert q.t_first is not None and q.first_tick is not None
        assert q.record()["first_tick"] == q.first_tick


def test_flight_record_carries_the_lane_tail(served, tmp_path,
                                             no_collection):
    path = tracing.dump(str(tmp_path / "flight.json"), reason="test")
    with open(path) as f:
        doc = json.load(f)
    assert 0 < len(doc["lanes"]) <= tracing.LANE_TAIL
    want = tracing.lane_log()[-tracing.LANE_TAIL:]
    assert [r["kind"] for r in doc["lanes"]] == [r["kind"] for r in want]
    # perf_counter stamps, so a reader can say how long ago each record was
    assert max(r[tracing._LANE_SPAN[r["kind"]][1]] for r in doc["lanes"]) \
        <= doc["now"] <= time.perf_counter()


# --- train dispatches --------------------------------------------------------

def _trainer():
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    net(nd.ones((2, 3)))
    return net, gluon.Trainer(net.collect_params(), "adam",
                              {"learning_rate": 1e-3})


def _fused_step():
    net, trainer = _trainer()
    loss_fn = gluon.loss.L2Loss()
    step = gluon.FusedTrainStep(
        net, trainer, lambda m, x, y: loss_fn(m(x), y),
        steps_per_execution=2, batch_size=2, stacked_inputs=True)
    return step, nd.ones((2, 2, 3)), nd.zeros((2, 2, 4))


def _fused_dispatches(n):
    step, x, y = _fused_step()
    for _ in range(n):
        step(x, y).asnumpy()
    return "fused", 2


def _per_step_dispatches(n):
    net, trainer = _trainer()
    loss_fn = gluon.loss.L2Loss()
    x, y = nd.ones((2, 3)), nd.zeros((2, 4))
    for _ in range(n):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(2)
    return "trainer.step", 1


@pytest.mark.parametrize("drive", [_fused_dispatches, _per_step_dispatches])
def test_train_dispatch_records(drive):
    since = time.perf_counter()
    path, k = drive(3)
    recs = [r for r in tracing.lane_log("train.dispatch", since=since)
            if r["path"] == path]
    assert [r["seq"] for r in recs] == [1, 2, 3]
    assert [r["k"] for r in recs] == [k] * 3
    assert [r["compiled"] for r in recs] == [True, False, False]
    for r in recs:
        assert r["t0"] <= r["t_args"] <= r["t_disp1"] <= r["t_end"]
    if path == "trainer.step":
        for r in recs:
            assert r["t_args"] == r["t_allreduce0"] <= r["t_allreduce1"] \
                == r["t_update0"] <= r["t_update1"] == r["t_disp1"]
    reader = _bench_module("layer_metrics", "train_dispatch_host_ms.py")
    host_ms = reader.read({"t0_abs": since,
                           "window_s": time.perf_counter() - since})
    assert host_ms == pytest.approx(
        np.median([(r["t_disp1"] - r["t0"]) * 1e3 for r in
                   tracing.lane_log("train.dispatch", since=since)]))


# --- the profiler's clock ----------------------------------------------------

def _tiny_block_decoder():
    from mxnet_tpu.models.sdar import sdar_moe_tiny

    net = sdar_moe_tiny()
    net.initialize()
    return net


def _traced_serve(tmp_path, make, **cfg):
    """Four requests through a tiny server under a CPU ``jax.profiler``
    trace -> ([a thread's ``mxt.*`` spans on ``/host:CPU`` as (name, start
    ns, duration ns, metadata), for each thread that has some], the lane
    records of the traced stretch).  The xplane has a line a thread, named
    for the OS thread (``python`` under CPython 3.12, which does not pass a
    ``threading.Thread``'s name on): a lane's line is told by the spans it
    holds.  A request's future resolves INSIDE its last tick
    (``mxt.decode.book``), so the trace must not start or stop on a result
    alone: a tick that straddles either end has its record in the log and
    its enclosing span outside the trace (that failed one run in a few
    under load).  The trace starts once the warm-up's last tick has written
    its record, and stops after the server has: its lanes are joined, every
    span is closed."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    srv = _server(make, **cfg)
    rs = np.random.RandomState(1)
    try:
        with srv:
            srv.generate(rs.randint(1, 250, size=6), max_new_tokens=2)
            lane = srv.replicas[0].decode
            for _ in range(3000):       # the warm-up's last record is in
                log = tracing.lane_log("decode.tick")
                if not lane.pending() and log \
                        and log[-1]["seq"] == srv.engine.steps:
                    break
                time.sleep(0.01)
            time.sleep(0.1)             # and its span, closed right after
            since = time.perf_counter()
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            for f in [srv.submit(rs.randint(1, 250, size=6), max_new_tokens=9)
                      for _ in range(4)]:
                f.result(120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            # the lanes' own spans: the collector's (``mxt.gc.pause``) open
            # in whichever thread set it off, between a lane's spans too
            events = [(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
                      for ev in line.events if ev.name.startswith("mxt.")
                      and ev.name != "mxt.gc.pause"]
            if events:
                lines.append(events)
    return lines, tracing.lane_log(since=since)


def _lane_line(lines, span):
    """The one thread whose line holds ``span``."""
    (events,) = [evs for evs in lines if any(e[0] == span for e in evs)]
    return events


@pytest.fixture(scope="module", params=[_tiny, _tiny_block_decoder],
                ids=["next_token", "block_diffusion"])
def traced(request, tmp_path_factory):
    return _traced_serve(tmp_path_factory.mktemp("xplane"), request.param)


def _by_name(lines):
    seen = {}
    for events in lines:
        for name, _start, _dur, stats in events:
            seen.setdefault(name, []).append(stats)
    return seen


def test_mxt_spans_land_in_the_xplane_with_the_logs_seq(traced):
    """Every tick and every prefill batch of the traced stretch has its
    spans in the xplane under the log's ``seq``."""
    lines, log = traced
    seen = _by_name(lines)
    ticks, batches = _kind(log, "decode.tick"), _kind(log, "prefill.batch")
    assert ticks and batches
    for name in ("mxt.decode.dispatch", "mxt.decode.fetch", "mxt.decode.book"):
        assert sorted(s["seq"] for s in seen[name]) \
            == [t["seq"] for t in ticks], name
        assert all(s["replica"] == 0 for s in seen[name])
    # a turn's span names the step it queues and the one it books: the same
    # step, but in the lane that runs a step ahead (a stretch's first turn
    # books none, its last queues none)
    turns = seen["mxt.decode.tick"]
    assert sorted(s["books"] for s in turns if s["books"]) \
        == [t["seq"] for t in ticks]
    assert all(s["replica"] == 0 and s["books"] in (0, s["seq"] - 1, s["seq"])
               for s in turns)
    for name in ("mxt.prefill.batch", "mxt.prefill.dispatch",
                 "mxt.prefill.fetch", "mxt.prefill.commit"):
        assert sorted(s["seq"] for s in seen[name]) \
            == [b["seq"] for b in batches], name


def test_wait_and_adopt_spans_carry_their_metadata(traced):
    """A turn crosses threads, so the xplane is linked by numbers:
    ``mxt.decode.adopt`` carries the tick's ``seq`` and the ``batch`` seqs
    whose requests that turn took up, as the ``slot.turn`` records do."""
    lines, log = traced
    seen = _by_name(lines)
    adopted = {}
    for turn in _kind(log, "slot.turn"):
        adopted.setdefault(turn["tick"], set()).add(turn["batch"])
    assert adopted and {s["seq"]: {int(b) for b in str(s["batch"]).split()}
                        for s in seen["mxt.decode.adopt"]} == adopted
    assert all(s["replica"] == 0 for s in seen["mxt.decode.adopt"])
    assert seen["mxt.decode.wait"] and all(
        s == {"replica": 0} for s in seen["mxt.decode.wait"])
    assert seen["mxt.prefill.wait"] and all(
        s["replica"] == 0 and s["reason"] in ("slot", "block", "tokens",
                                              "empty")
        for s in seen["mxt.prefill.wait"])
    # two slots, four requests: the later ones waited for a slot, and for
    # nothing else
    gated = {g["reason"] for g in _kind(log, "prefill.gated")}
    assert {s["reason"] for s in seen["mxt.prefill.wait"]} - {"empty"} \
        == gated == {"slot"}
    # the adopt span lies inside its turn's tick span
    decode = _lane_line(lines, "mxt.decode.tick")
    # (a turn that queues nothing leaves its ``seq`` to the next one)
    ticks_of = {}
    for name, t0, dur, st in decode:
        if name == "mxt.decode.tick":
            ticks_of.setdefault(st["seq"], []).append((t0, t0 + dur))
    for name, t0, dur, st in decode:
        if name == "mxt.decode.adopt":
            assert any(lo <= t0 and t0 + dur <= hi
                       for lo, hi in ticks_of[st["seq"]])


def test_a_lane_thread_is_always_under_a_top_level_span(traced):
    """Between a lane thread's first and last span every instant is under
    ``mxt.prefill.batch`` or ``mxt.prefill.wait``, ``mxt.decode.tick`` or
    ``mxt.decode.wait``, but for the few lines of the gate: to 98%."""
    lines, _log = traced
    assert len(lines) == 2      # the two lanes; the draft has no thread
    for names in TOP_LEVEL:
        events = _lane_line(lines, names[0])
        assert {e[0].split(".")[1] for e in events} \
            == {names[0].split(".")[1]}     # a lane's spans on its own line
        top = sorted((t0, t0 + dur) for name, t0, dur, _st in events
                     if name in names)
        assert {name for name, *_ in events if name in names} == set(names)
        # every other span of the thread lies inside a top-level one
        for name, t0, dur, _st in events:
            if name not in names:
                assert any(lo <= t0 and t0 + dur <= hi for lo, hi in top), name
        covered, edge = 0, top[0][0]
        for lo, hi in top:
            assert lo >= edge       # top-level spans of one thread never overlap
            covered += hi - lo
            edge = hi
        assert covered >= 0.98 * (top[-1][1] - top[0][0]), (
            names, covered / (top[-1][1] - top[0][0]))


def test_the_speculative_tick_books_under_its_span_and_the_draft_has_its_names(
        tmp_path):
    """``_tick_spec``: ``k`` draft steps under ``mxt.draft.dispatch`` /
    ``mxt.draft.fetch`` (the draft engine's own ``seq``), then one verify
    under ``mxt.decode.dispatch`` / ``.fetch`` and the bookkeeping under
    ``mxt.decode.book``, all three with the tick's ``seq``."""
    k = 2
    net = _tiny()
    lines, log = _traced_serve(tmp_path, lambda: net, draft_net=net, spec_k=k)
    seen = _by_name(lines)
    ticks = _kind(log, "decode.tick")
    assert ticks and all("accepted" in t for t in ticks)
    for name in ("mxt.decode.tick", "mxt.decode.dispatch", "mxt.decode.fetch",
                 "mxt.decode.book"):
        assert sorted(s["seq"] for s in seen[name]) \
            == [t["seq"] for t in ticks], name
    for name in ("mxt.draft.dispatch", "mxt.draft.fetch"):
        seqs = sorted(s["seq"] for s in seen[name])
        assert len(seqs) == k * len(ticks), name
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert len(_kind(log, "slot.turn")) == sum(t["n_adopted"] for t in ticks)


def test_the_speculative_tick_stays_serial():
    """``_tick_spec`` calls ``step()`` / ``verify()`` whole: no record says
    ``ahead``, and the step's own dispatch stamps are the turn's."""
    net = _tiny()
    _reqs, log, _stats, _eng = _serve(3, 6, lambda: net, draft_net=net,
                                      spec_k=2)
    ticks = _kind(log, "decode.tick")
    assert ticks and all("accepted" in t for t in ticks)
    assert not any(t["ahead"] for t in ticks)
    for t in ticks:
        assert [t["t_step_" + s[2:]] for s in TICK_STAMPS[:4]] \
            == [t[s] for s in TICK_STAMPS[:4]]


# --- names the benchmark reads -----------------------------------------------

def test_compiled_program_names_match_the_benchmarks_regexes():
    """``chipbench/families`` finds the device programs in the profiler's
    ``XLA Modules`` line by ``^jit__step_fn`` etc.: the names are a
    contract.  A rename has to come with a ``benchmark`` PR."""
    import re

    eng = serving.GenerativeServer(_tiny(), ServerConfig(
        max_batch=2, max_length=64, min_length=8, num_slots=2)).engine
    ids = np.ones((1, 8), np.int32)
    t0s = np.full(1, 6, np.int32)
    _toks, rows = eng.prefill_rows(ids, t0s)
    flat = np.full(2, eng.num_blocks, np.int32)
    lowered = {
        "step": eng._step.lower(eng._w, eng._pool, eng._dev(eng._tables),
                                eng._dev(eng._last), eng._toks,
                                eng._dev(eng._pos)),
        "prefill": eng._prefill.lower(eng._w, eng._dev(ids), eng._dev(t0s)),
        "scatter": eng._scatter.lower(eng._pool, rows, eng._dev(flat)),
    }
    programs = _bench_module("families", "llama.py").PROGRAMS
    assert set(programs) == set(lowered)
    for key, low in lowered.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)
    assert programs == {"step": r"^jit__step_fn", "prefill": r"^jit__prefill_fn",
                        "scatter": r"^jit__scatter_fn"}
    fused = _bench_module("families", "bert.py").Cell.programs["fused_step"]
    step, x, y = _fused_step()
    name = re.search(r"module @(\S+)", step.lower(x, y).as_text()).group(1)
    assert fused == r"^jit_k_steps" and re.search(fused, name), name


def test_recording_calls_are_registered_with_the_lint():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint.rules import _is_recording_call

    assert _is_recording_call("tracing.lane_record")
    assert _is_recording_call("TraceAnnotation")
    assert not _is_recording_call("time.perf_counter")

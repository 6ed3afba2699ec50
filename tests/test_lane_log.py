"""The lane log (``telemetry.tracing``): always-on tick, prefill-batch and
train-dispatch records, the prefill lane's busy / gated / idle clock, the
``mxt.*`` spans on the profiler's clock, and the names the benchmark's
readers hold the program to.

Everything runs a tiny llama server or a tiny trainer on the CPU; a stamp is
only ever compared with another stamp, never read as a time.
"""
import glob
import importlib.util
import json
import os
import sys
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


def _tiny():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


def _serve(n_requests=5, new_tokens=5, **cfg):
    """Run ``n_requests`` through a tiny server -> (requests, the lane
    records it wrote, its stats, the engine)."""
    kw = dict(max_batch=2, max_length=64, min_length=8, num_slots=2)
    kw.update(cfg)
    srv = serving.GenerativeServer(_tiny(), ServerConfig(**kw))
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


def test_gated_rises_when_two_requests_queue_for_one_slot():
    _reqs, log, stats, _eng = _serve(n_requests=3, new_tokens=8,
                                     num_slots=1, max_batch=1)
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


# --- the ring ----------------------------------------------------------------

def test_ring_is_bounded_and_lane_log_filters():
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


def test_flight_record_carries_the_lane_tail(served, tmp_path):
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


@pytest.mark.parametrize("make", [_tiny, _tiny_block_decoder],
                         ids=["next_token", "block_diffusion"])
def test_mxt_spans_land_in_the_xplane_with_the_logs_seq(tmp_path, make):
    """Every tick and every prefill batch of the traced stretch has its
    spans in the xplane under the log's ``seq``.  A request's future
    resolves INSIDE its last tick (``mxt.decode.book``), so the trace
    must not start or stop on a result alone: a tick that straddles
    either end has its record in the log and its enclosing span outside
    the trace (that failed one run in a few under load).  The trace
    starts once the warm-up's last tick has written its record, and
    stops after the server has: its lanes are joined, every span is
    closed."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    srv = serving.GenerativeServer(make(), ServerConfig(
        max_batch=2, max_length=64, min_length=8, num_slots=2))
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
            for f in [srv.submit(rs.randint(1, 250, size=6), max_new_tokens=4)
                      for _ in range(3)]:
                f.result(120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mxt."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert ticks and batches
    for name in ("mxt.decode.tick", "mxt.decode.dispatch", "mxt.decode.fetch",
                 "mxt.decode.book"):
        assert sorted(s["seq"] for s in seen[name]) \
            == [t["seq"] for t in ticks], name
        assert all(s["replica"] == 0 for s in seen[name])
    for name in ("mxt.prefill.batch", "mxt.prefill.dispatch",
                 "mxt.prefill.fetch", "mxt.prefill.commit"):
        assert sorted(s["seq"] for s in seen[name]) \
            == [b["seq"] for b in batches], name


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
                                eng._dev(eng._last), eng._dev(eng._pos)),
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

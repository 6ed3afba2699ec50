"""The decode tick one step ahead of its bookkeeping (``serving/lanes.py``
``DecodeLane._tick`` over the two halves of ``LlamaServingEngine.step``): step
K+1 is queued from the device's own tokens before the host has fetched step
K's; a block decoder's pass K+1 from the blocks, masks, pass counts and
cursors that pass K booked for itself on the device.

Held here, on tiny models on the CPU: a request's tokens (a block decoder's
commits too) are the serial ``step()`` loop's, token for token, whatever
finishes, is admitted or takes over a freed slot's blocks while a step is
queued; an exception in either half fails each request once and frees every
slot; the lane log still means what the benchmark's readers
(``chipbench/lane_spans.py``, ``turn_spans.py``, imported as they stand) take
it to mean; and the verify program, whose tick stays serial, lowers as before.
"""
import hashlib
import os
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import serving
from mxnet_tpu.serving import ServerConfig, generative
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")

#: a dense paged model, LFM2's ring, the gated delta rule's float32 state,
#: two models that select the keys a query reads (8 of them: ``index_topk``):
#: out of a latent cache, and out of K/V pools
MODELS = ("llama_tiny", "lfm2_moe_tiny", "qwen3_next_tiny",
          "glm_moe_dsa_tiny", "keye_vl2_tiny")
_MODULE = {"llama_tiny": "llama", "lfm2_moe_tiny": "lfm2",
           "qwen3_next_tiny": "qwen3_next", "glm_moe_dsa_tiny": "glm_moe_dsa",
           "keye_vl2_tiny": "keye_vl2", "sdar_moe_tiny": "sdar"}
SLOTS, BLOCK, MAX_LEN = 3, 4, 64


def _make(name):
    import importlib

    net = getattr(importlib.import_module(
        "mxnet_tpu.models." + _MODULE[name]), name)()
    net.initialize()
    return net


class _Model:
    """A tiny net and one engine of the test's shapes, driven by hand: the
    serial reference and the halves run apart (slots cleared between)."""

    def __init__(self, name):
        self.net = _make(name)
        self.eng = _server(self.net).engine

    def cleared(self):
        for slot in range(SLOTS):
            self.eng.clear_slot(slot)
        return self.eng

    def serial(self, prompt, n_new, slot=0):
        """The serial loop: the request alone in the engine, each
        ``step()`` fetched and booked before the next is dispatched."""
        eng = self.cleared()
        out = [_commit(eng, slot, prompt, _blocks(eng, slot))]
        while len(out) < n_new:
            out.append(int(eng.step([slot])[slot]))
        return out


@pytest.fixture(scope="module", params=MODELS)
def model(request):
    return _Model(request.param)


def _server(net, **kw):
    cfg = dict(max_batch=1, max_length=MAX_LEN, min_length=8,
               num_slots=SLOTS, block_size=BLOCK)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


def _commit(eng, slot, prompt, blocks):
    """Prefill ``prompt`` at its bucket, a batch of one, and commit it into
    ``slot`` over ``blocks`` -> its first token."""
    t0 = len(prompt)
    lb = max(8, 1 << (t0 - 1).bit_length())
    ids = np.zeros((1, lb), np.int32)
    ids[0, :t0] = prompt
    t0s = np.asarray([t0], np.int32)
    toks, rows = eng.prefill_rows(ids, t0s)
    first, _counts = eng.split_fetch(np.asarray(toks), 1)
    eng.commit_rows(rows, np.asarray([slot]), [blocks], t0s, first)
    # a block decoder's prefill yields the block it opens, not a token
    return int(first[0]) if first.ndim == 1 else first[0]


def _blocks(eng, slot):
    return list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 250, size=n).astype(np.int32) for n in lengths]


# --- the engine's halves, driven by hand --------------------------------------

def test_halves_run_ahead_equal_the_serial_loop(model):
    """Slot 0 decodes throughout.  Slot 1's request ends with step 3: step 4
    is queued without it before step 3's tokens are fetched, the slot is
    released as they are booked and ITS BLOCKS go straight to the next
    prompt, whose commit is queued behind step 4 and which joins step 5,
    queued before step 4 is fetched.  The device runs programs in the order
    they were queued, so every token is the serial loop's."""
    a, b, c = _prompts(11, (9, 5, 13))
    eng = model.cleared()
    got = {"a": [_commit(eng, 0, a, _blocks(eng, 0))],
           "b": [_commit(eng, 1, b, _blocks(eng, 1))], "c": []}
    owner = {0: "a", 1: "b"}

    def book(step):
        toks = eng.fetch_step(step)
        for s in step.active:
            got[owner_at[step.seq][int(s)]].append(int(toks[s]))

    owner_at = {}

    def queue(active):
        step = eng.dispatch_step(active)
        owner_at[step.seq] = dict(owner)
        return step

    s1 = queue([0, 1])
    s2 = queue([0, 1])            # ahead: step 1 not fetched
    assert not s1.ahead and s2.ahead
    book(s1)
    s3 = queue([0, 1])            # b's last token: 1 + 3 = 4 in all
    book(s2)
    s4 = queue([0])               # b left out: a vacant row of step 4
    book(s3)
    assert len(got["b"]) == 4
    eng.clear_slot(1)             # released as step 3 is booked ...
    owner[1] = "c"                # ... and its blocks handed on at once
    got["c"].append(_commit(eng, 1, c, _blocks(eng, 1)))
    assert eng.step_in_flight == s4.seq
    s5 = queue([0, 1])            # c's first step, queued behind step 4
    book(s4)
    s6 = queue([0, 1])
    book(s5)
    book(s6)
    assert eng.step_in_flight is None and eng.booked is s6
    assert got["a"] == model.serial(a, 7)
    assert got["b"] == model.serial(b, 4, slot=1)
    assert got["c"] == model.serial(c, 3, slot=1)


def test_a_slot_the_host_wrote_takes_the_hosts_token(model):
    """``set_mirror`` between two steps: the next step reads the host's
    token for that slot and the device's own for its neighbour."""
    a, b = _prompts(12, (6, 7))

    def two_steps(all_from_host):
        eng = model.cleared()
        _commit(eng, 0, a, _blocks(eng, 0))
        _commit(eng, 1, b, _blocks(eng, 1))
        first = eng.step([0, 1])
        eng.set_mirror(1, (int(first[1]) + 1) % 250, eng.positions()[1])
        if all_from_host:         # every id uploaded, as before this tick
            eng._fresh[:] = True
        return eng.step([0, 1])[:2]

    got = two_steps(False)
    assert np.array_equal(got, two_steps(True))
    assert got[0] == model.serial(a, 3)[2]


def test_pos_moves_at_dispatch_and_the_handle_keeps_its_own(model):
    eng = model.cleared()
    a, = _prompts(13, (10,))
    _commit(eng, 2, a, _blocks(eng, 2))
    s1 = eng.dispatch_step([2])
    assert eng.positions()[2] == 11 == s1.pos[2] and s1.kv_tokens == 11
    s2 = eng.dispatch_step([2])
    assert (s1.pos[2], s2.pos[2], s2.kv_tokens) == (11, 12, 12)
    assert (s1.seq + 1, s2.seq) == (s2.seq, eng.steps)
    eng.fetch_step(s1)
    assert eng.booked is s1 and eng.step_in_flight == s2.seq
    eng.fetch_step(s2)
    assert s1.t_disp1 <= s2.t_disp0 and s1.t_tok <= s2.t_tok
    if eng.cache_spec.select_topk:
        # what step 1 read is step 1's, after step 2 has run
        k = eng.cache_spec.select_topk
        picked = eng.selection_of(2, s1)
        assert picked.shape[-1] == k and (picked >= 0).sum(-1).max() == k
        assert picked.max() == 10 and eng.selection_of(2, s2).max() == 11
        assert (s1.selection["kv_visible"], s1.selection["kv_selected"]) \
            == (11, k)


# --- through the lanes ---------------------------------------------------------

def test_served_tokens_are_the_serial_loops(model, monkeypatch):
    """Two slots and a pool of 20 blocks.  A lone request; then A (50
    tokens, 15 blocks) decodes throughout.  B arrives with the lane held in
    a fetch and a step queued behind it: its prefill is committed behind
    that step; B ends mid-stream; C needs 5 blocks where 2 are free, so it
    waits for B's slot and takes B's blocks as they are released; D follows
    C.  While an admission is pending the lane leaves the device's queue
    to the prefill's forward (``_prefill_covers``).  Every token is the
    serial loop's."""
    import threading

    hold, held = threading.Event(), threading.Event()
    real_fetch = generative._materialize

    def gated_fetch(arrays):
        if hold.is_set():
            held.set()
            while hold.is_set():
                time.sleep(0.001)
        return real_fetch(arrays)

    monkeypatch.setattr(generative, "_materialize", gated_fetch)
    lone, a, b, c, d = _prompts(21, (7, 9, 5, 13, 3))
    n_new = {"lone": 9, "a": 50, "b": 4, "c": 6, "d": 5}
    since = time.perf_counter()
    futs = {}
    try:
        with _server(model.net, num_slots=2, num_blocks=20) as srv:
            lane = srv.replicas[0].decode
            futs["lone"] = srv.submit(lone, max_new_tokens=n_new["lone"])
            futs["lone"].result(180)
            alone = tracing.lane_log("decode.tick", since=since)
            futs["a"] = srv.submit(a, max_new_tokens=n_new["a"])
            while futs["a"].request.first_tick is None:
                time.sleep(0.001)
            # hold the lane in a fetch: nothing waits for a slot, so it has
            # queued the step after; B is prefilled and committed behind it
            hold.set()
            assert held.wait(60) and lane._flight is not None
            queued = lane._flight.step.seq
            assert srv.engine.step_in_flight == queued
            futs["b"] = srv.submit(b, max_new_tokens=n_new["b"])
            while futs["b"].request.t_commit is None:
                time.sleep(0.001)
            hold.clear()

            def held_until_committed(key):
                """A's decoding waits in a fetch while ``key`` is prefilled
                (its bucket's first compile takes a second)."""
                held.clear()
                hold.set()
                assert held.wait(60)
                while futs[key].request.t_commit is None:
                    time.sleep(0.001)
                hold.clear()

            futs["c"] = srv.submit(c, max_new_tokens=n_new["c"])
            futs["d"] = srv.submit(d, max_new_tokens=n_new["d"])
            futs["b"].result(180)
            held_until_committed("c")
            futs["c"].result(180)
            held_until_committed("d")
            outs = {k: f.result(180) for k, f in futs.items()}
            stats = srv.stats()
    finally:
        hold.clear()
    assert stats["failed"] == 0 and stats["completed"] == 5
    # the first step's ``prev`` is committed as a step's output is: the
    # step program compiled once for all 60-odd steps
    assert srv.engine._step._cache_size() == 1
    prompts = {"lone": lone, "a": a, "b": b, "c": c, "d": d}
    for k, p in prompts.items():
        assert (outs[k][:len(p)] == p).all()
        assert outs[k][len(p):].tolist() == model.serial(
            p, n_new[k], slot=futs[k].request.slot), k
    # a lone slot runs ahead from its second step on
    assert [t["seq"] for t in alone] == list(range(1, 9))
    assert [t["ahead"] for t in alone] == [False] + [True] * 7
    assert all(t["n_active"] == 1 for t in alone)
    ids = {k: f.request.id for k, f in futs.items()}
    ticks = tracing.lane_log("decode.tick", since=since)[len(alone):]
    turns = {t["request_id"]: t
             for t in tracing.lane_log("slot.turn", since=since)}
    by_seq = {t["seq"]: t for t in ticks}
    # while A decodes the lane never stands empty: but for A's first step
    # and a few around each admission, a step was queued before the one
    # ahead of it was fetched
    with_a = [t for t in ticks if ids["a"] in t["request_ids"]]
    assert len(with_a) == 49 and not with_a[0]["ahead"]
    assert sum(t["ahead"] for t in with_a) >= 30
    assert by_seq[queued]["ahead"] and by_seq[queued]["n_active"] == 1
    for k in "bcd":          # each admitted beside A
        first = by_seq[turns[ids[k]]["tick"]]
        assert (first["n_adopted"], first["n_active"]) == (1, 2), k
    # B's prefill was committed behind the step that was queued then
    assert turns[ids["b"]]["tick"] > queued
    # B ended mid-stream: left out of the next step
    last_b = [t for t in ticks if ids["b"] in t["request_ids"]][-1]
    assert (last_b["n_finished"], last_b["n_active"]) == (1, 2)
    assert by_seq[last_b["seq"] + 1]["request_ids"] == (ids["a"],)
    # and its slot and blocks went straight to C, then C's to D
    assert turns[ids["c"]]["prev_request_id"] == ids["b"]
    assert turns[ids["c"]]["freed_by"] == last_b["seq"]
    assert turns[ids["d"]]["prev_request_id"] == ids["c"]
    assert futs["b"].request.slot == futs["c"].request.slot


@pytest.mark.parametrize("half", ["dispatch", "fetch"])
def test_an_exception_in_either_half_fails_each_request_once(half, monkeypatch):
    """Two steps may be in flight when a half raises: the requests of both
    fail, each once, every slot and block comes back, no step is left in
    flight, and the server goes on serving."""
    net = _make("llama_tiny")
    srv = _server(net, num_slots=2)
    eng, rep = srv.engine, srv.replicas[0]
    prompts = _prompts(23, (6, 9, 5))
    real_fetch, real_step = generative._materialize, eng._step
    armed = {"on": False, "hold": False}

    def planted(real, what):
        def call(*args):
            if armed["on"]:
                armed["on"] = False
                raise RuntimeError("planted: the %s half" % what)
            return real(*args)
        return call

    def held_fetch(*args):
        while armed["hold"]:
            time.sleep(0.001)
        return (planted(real_fetch, "fetch") if half == "fetch"
                else real_fetch)(*args)

    with srv:
        monkeypatch.setattr(generative, "_materialize", held_fetch)
        if half == "dispatch":
            eng._step = planted(real_step, "dispatch")
        lost = [srv.submit(prompts[0], max_new_tokens=50)]
        while lost[0].request.first_tick is None:
            time.sleep(0.001)
        armed["hold"] = True          # the first waits while the second
        lost.append(srv.submit(prompts[1], max_new_tokens=50))
        while lost[1].request.t_commit is None:
            time.sleep(0.001)         # ... is prefilled and committed
        armed["hold"] = False
        while lost[1].request.first_tick is None:
            time.sleep(0.001)         # both decode: two steps in flight
        armed["on"] = True
        for f in lost:
            with pytest.raises(RuntimeError, match="planted"):
                f.result(120)
        monkeypatch.setattr(generative, "_materialize", real_fetch)
        eng._step = real_step
        for _ in range(5000):         # the count follows the future
            if rep.failed == 2:
                break
            time.sleep(0.001)
        assert rep.failed == 2 and srv.stats()["failed"] == 2
        assert eng.step_in_flight is None and rep.decode._flight is None
        assert rep.mgr.free_slots() == 2
        assert rep.mgr.allocator.blocks_in_use == 0
        kept = srv.submit(prompts[2], max_new_tokens=5)
        out = kept.result(120)
        assert rep.failed == 2 and rep.completed == 1
    ref = _server(net, num_slots=2).engine
    want = [_commit(ref, 0, prompts[2], _blocks(ref, 0))]
    while len(want) < 5:
        want.append(int(ref.step([0])[0]))
    assert out[len(prompts[2]):].tolist() == want
    assert rep.mgr.check()


class _Stub:
    """Whatever of a replica, its engine and its manager a turn of the lane
    touches, by hand: the decision a turn takes is a matter of what the
    prefill lane has on the device's queue, which a server cannot pin."""

    index, queue, prefill_in_flight, blocks_in_use, block_size = 0, (), (), 0, 4
    gate = None          # the prefill lane's: what its last look waited for

    def __init__(self, budget):
        self.engine = self.mgr = self.allocator = self.prefill = self
        self.steps = self.batches = self.steps_ahead = 0
        self.left, self.done = dict(budget), {}

    def dispatch_step(self, active):
        self.steps += 1
        now = time.perf_counter()
        return generative.StepHandle(
            self.steps, np.asarray(active, np.intp),
            {s: 10 * self.steps + s for s in active}, now, now, now,
            behind=self.prefill_in_flight)

    def fetch_step(self, step):
        step.t_tok = time.perf_counter()
        return step.toks

    def grant_step(self, slots, n=1):
        return {}, []        # every slot holds the block it writes

    def advance(self, slot):
        pass

    def consume(self, slot):
        self.left[slot] -= 1
        return self.left[slot] <= 0

    def free_slots(self):
        return 1

    def finish(self, req, tokens, step=None):
        self.done[req.id] = (list(tokens), step)


class _Req:
    trace = first_tick = t_handoff = None
    t_start = t_commit = 0.0

    def __init__(self, rid):
        self.id = rid


def test_a_covered_turn_queues_nothing_and_the_hand_off_rides_the_next_step():
    from mxnet_tpu.serving.lanes import DecodeLane, _Handoff

    r = _Stub({0: 4, 1: 2})
    lane = DecodeLane(r)
    since = time.perf_counter()

    def turn():
        lane._adopt()
        lane._tick()
        return lane._flight.step.seq if lane._flight else None

    lane.hand_off(_Handoff(_Req("a"), 0, 1))
    assert turn() == 1                    # nothing in flight: queued, not booked
    assert turn() == 2                    # step 2 ahead, step 1 booked
    # a forward is queued behind step 2: the turn queues nothing and books
    # step 2; the hand-off it adopted waits for a step
    r.prefill_in_flight = (7,)
    lane.hand_off(_Handoff(_Req("b"), 1, 5))
    assert turn() is None and r.steps == 2
    # the next turn queues step 3 behind that forward, with the new slot
    assert turn() == 3 and lane._flight.step.behind == (7,)
    assert [h.req.id for h in lane._flight.adopted] == ["b"]
    # step 3 ran behind the forward, so it covers nothing more: step 4 is
    # queued ahead of step 3's booking ... unless a request waits for a slot
    r.queue = ("c",)
    assert turn() is None and r.steps == 3
    r.queue, r.prefill_in_flight = (), ()
    assert turn() == 4 and turn() is None     # each slot's last step
    assert r.done == {"b": ([5, 31, 41], 4), "a": ([1, 10, 20, 30, 40], 4)}
    ticks = tracing.lane_log("decode.tick", since=since)
    assert [(t["seq"], t["ahead"], t["n_adopted"]) for t in ticks] == [
        (1, False, 1), (2, False, 0), (3, False, 1), (4, False, 0)]
    (ta, tb) = tracing.lane_log("slot.turn", since=since)
    assert (ta["request_id"], ta["tick"], tb["request_id"], tb["tick"]) \
        == ("a", 1, "b", 3)
    assert tb["t_adopt"] < ticks[2]["t_step_loop"] and tb["t_tok"] \
        == ticks[2]["t_tok"]


def test_a_lane_gated_on_blocks_covers_nothing():
    """A request waits and a slot is free, but the prefill lane waits for
    BLOCKS (a pool smaller than slots x max_length): no forward is coming, so
    the turn queues its step ahead (PR 40)."""
    from mxnet_tpu.serving.lanes import DecodeLane, _Handoff

    r = _Stub({0: 6})
    r.queue = ("c",)
    lane = DecodeLane(r)

    def turn():
        lane._adopt()
        lane._tick()
        return lane._flight.step.seq if lane._flight else None

    lane.hand_off(_Handoff(_Req("a"), 0, 1))
    assert turn() == 1
    assert turn() is None and r.steps == 1     # about to admit: covered
    r.gate = "block"
    assert turn() == 2 and turn() == 3 and turn() == 4
    assert lane._flight.step.ahead is False    # the stub's steps say nothing
    r.gate = "slot"
    assert turn() is None and r.steps == 4


# --- the lane log under the benchmark's readers, as they stand -----------------

@pytest.fixture(scope="module")
def readers():
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import lane_spans
    import turn_spans

    return lane_spans, turn_spans


@pytest.fixture(scope="module")
def window():
    """A CPU window of the lane, its programs compiled before it: two
    requests that fill both slots and decode with nothing waiting (the lane
    runs ahead), then five that queue for the slots as they free (an
    admission is pending at most turns), then the lane stands empty, then
    two more."""
    net = _make("llama_tiny")
    with _server(net, num_slots=2) as srv:
        lane = srv.replicas[0].decode

        def drained():
            while lane.pending():
                time.sleep(0.001)
            time.sleep(0.05)

        for p in _prompts(30, (6, 9)):
            srv.generate(p, max_new_tokens=2)
        drained()
        t0, steps0 = time.perf_counter(), srv.engine.steps
        first = [srv.submit(p, max_new_tokens=50)
                 for p in _prompts(31, (6, 9))]
        while any(f.request.first_tick is None for f in first):
            time.sleep(0.0005)
        both = max(f.request.first_tick for f in first)
        while srv.engine.steps < both + 20:
            time.sleep(0.0005)
        quiet_to = srv.engine.steps    # queued with nothing waiting
        more = [srv.submit(p, max_new_tokens=n) for p, n in
                zip(_prompts(33, (5, 12, 7, 3, 10)), (4, 7, 3, 8, 5))]
        for f in first + more:
            f.result(180)
        drained()
        later = [srv.submit(p, max_new_tokens=5)
                 for p in _prompts(32, (5, 11))]
        for f in later:
            f.result(180)
        steps = (steps0, srv.engine.steps)
    obs = {"t0_abs": t0, "window_s": time.perf_counter() - t0}
    marks = {"quiet": (both + 2, quiet_to),
             "later": {f.request.id for f in later}}
    return obs, [f.request for f in first + more + later], steps, marks


def test_tick_phases_stay_non_negative_and_host_under_the_period(readers,
                                                                  window):
    lane_spans, _ = readers
    obs, _reqs, (steps0, steps), _marks = window
    rows = lane_spans.tick_phases_ms(obs)
    assert len(rows) == steps - steps0 - 1
    for row in rows:
        assert all(row[name] >= 0 for name in lane_spans.TICK_PHASES), row
        assert 0 <= row["host"] <= row["period"]
        assert sum(row[n] for n in lane_spans.TICK_PHASES) \
            == pytest.approx(row["period"], abs=1e-6)
    host = lane_spans.tick_host_ms(obs)
    assert 0 <= host <= max(r["period"] for r in rows)


def test_token_gaps_are_positive_and_one_a_token(readers, window):
    lane_spans, _ = readers
    obs, reqs, _steps, _marks = window
    gaps = lane_spans.token_gaps(obs)
    assert set(gaps) == {q.id for q in reqs}
    for q in reqs:
        # the first token is the prefill's; every other has the gap before it
        assert len(gaps[q.id]) == q.max_new_tokens - 1
        assert all(g > 0 for g in gaps[q.id])


def test_every_turn_is_ordered_and_ends_with_its_first_steps_tokens(readers,
                                                                    window):
    lane_spans, turn_spans = readers
    obs, reqs, _steps, _marks = window
    turns = turn_spans.turns(obs)
    ticks = {t["seq"]: t for t in lane_spans.records(obs, "decode.tick")}
    assert sorted(t["request_id"] for t in turns) == sorted(q.id for q in reqs)
    assert len(turn_spans.turns(obs, released=True)) == len(reqs) - 2
    for turn in turns:
        stamps = [turn[s] for s in ("t_start", "t_first", "t_handoff",
                                    "t_adopt", "t_tok")]
        assert stamps == sorted(stamps)
        assert turn["t_free"] is None or turn["t_free"] <= turn["t_start"]
        tick = ticks[turn["tick"]]
        # the first step that carried the slot: the one before did not
        assert turn["request_id"] in tick["request_ids"]
        before = ticks.get(turn["tick"] - 1)
        assert before is None \
            or turn["request_id"] not in before["request_ids"]
        assert turn["t_tok"] == tick["t_tok"]
        # adopted in the turn that queued that step, or in one before it
        # that queued none: after the step before was queued
        assert turn["t_adopt"] <= tick["t_step_lock"]
        assert before is None \
            or before["t_step_lock"] <= turn["t_adopt"]
    assert sum(t["n_adopted"] for t in ticks.values()) == len(turns)
    assert turn_spans.median_ms(turns, "t_adopt", "t_tok") > 0
    behind, clear = turn_spans.ticks_by_behind(obs)
    assert len(behind) + len(clear) == len(ticks)


def test_ahead_where_nothing_waits_for_a_slot(readers, window):
    """``ahead`` is true on every step but a stretch's first and those the
    lane left to a prefill's forward: with both slots held and nothing
    queued every step runs ahead; the first step after an empty lane never
    does."""
    lane_spans, _ = readers
    obs, _reqs, (steps0, steps), marks = window
    ticks = lane_spans.records(obs, "decode.tick")
    assert [t["seq"] for t in ticks] == list(range(steps0 + 1, steps + 1))
    assert not ticks[0]["ahead"]
    for a, b in zip(ticks, ticks[1:]):
        # queued before the step ahead of it had been fetched, or not
        assert b["ahead"] == (b["t_step_disp1"] < a["t_tok"])
        # the step's own dispatch lies a turn before the turn's
        assert b["t_step_disp1"] <= b["t_disp0"] or not b["ahead"]
    # both slots held and nothing queued: from the second step after the
    # later hand-off's first to the last one queued before more arrived
    lo, hi = marks["quiet"]
    quiet = [t for t in ticks if lo <= t["seq"] <= hi]
    assert len(quiet) >= 15 and all(t["n_active"] == 2 for t in quiet)
    assert all(t["ahead"] for t in quiet)
    # the lane stood empty before the last two requests
    after = [t for t in ticks if set(t["request_ids"]) <= marks["later"]]
    assert after and not after[0]["ahead"]
    assert after[0]["n_adopted"] == after[0]["n_active"]
    # and at some turn it left the queue to a prefill's forward
    assert any(not b["ahead"] and a["n_finished"] < a["n_active"]
               for a, b in zip(ticks, ticks[1:]))


def test_the_counters_and_the_summary_say_the_share(monkeypatch):
    from mxnet_tpu import telemetry

    seen = []
    monkeypatch.setattr(telemetry, "count",
                        lambda name, n=1: seen.append((name, n)))
    net = _make("llama_tiny")
    with _server(net) as srv:
        srv.generate(_prompts(41, (6,))[0], max_new_tokens=11)
        rep = srv.replicas[0]
        assert srv.stats()["decode_steps_ahead"] == 9
        emitted = []
        monkeypatch.setattr(telemetry, "emit", emitted.append)
        rep.emit_summary()
    steps = sum(n for name, n in seen if name == "serving.decode.steps")
    ahead = sum(n for name, n in seen if name == "serving.decode.steps_ahead")
    assert (steps, ahead) == (10, 9) == (rep.batches, rep.steps_ahead)
    assert emitted[-1]["steps_ahead_share"] == 0.9


# --- the tick that stays serial -------------------------------------------------

#: sha256 (16 hex digits) of the lowered text of the tiny Llama's verify
#: program on the commit before the tick ran ahead (PR 38, e24d6dc):
#: ``_tick_spec`` calls ``verify()`` as before, and its program is the
#: parent's letter for letter.  (The token-at-a-time step takes one more
#: argument, the step before's output: ``tests/test_sdar.py``
#: ``PARENT_PROGRAMS``; a block pass, since it runs ahead too, the pass
#: before's own booking and one array of the host's rows.)
SERIAL_PROGRAMS = {"llama_tiny": "d321f3b3237cf784"}


@pytest.mark.parametrize("model", sorted(SERIAL_PROGRAMS))
def test_the_verify_program_lowers_as_before(model):
    eng = _server(_make(model), max_batch=2).engine
    low = eng._verify.lower(
        eng._w, eng._pool, eng._dev(eng._tables),
        eng._dev(np.zeros((eng.num_slots, 3), np.int32)), eng._dev(eng._pos))
    assert hashlib.sha256(low.as_text().encode()).hexdigest()[:16] \
        == SERIAL_PROGRAMS[model]


# --- a block decoder's pass, a pass ahead of its bookkeeping ---------------------

BL, MASK = 4, 255        # the tiny SDAR's block length and mask id


class _Taken:
    """What a request takes of its passes, as the lane books them: the tokens
    at its first ``n_new`` positions behind the prompt and every commit
    ``(position, token, the block's pass)``."""

    def __init__(self, prompt, n_new):
        self.n_prompt, self.n_new = len(prompt), n_new
        self.out, self.commits = {}, []

    def take(self, tick, slot):
        """-> whether the pass committed the last of its positions."""
        for j in np.flatnonzero(tick.commit[slot]):
            pos, tok = int(tick.pos0[slot]) + int(j), int(tick.ids[slot, j])
            self.commits.append((pos, tok, int(tick.step[slot])))
            if pos - self.n_prompt < self.n_new:
                self.out[pos - self.n_prompt] = tok
        return len(self.out) == self.n_new

    def tokens(self):
        return [self.out[i] for i in range(self.n_new)]


class _Block:
    """The tiny block decoder and one engine of six slots, driven by hand."""

    def __init__(self):
        self.net = _make("sdar_moe_tiny")
        self.eng = _server(self.net, num_slots=6).engine

    def cleared(self):
        for slot in range(self.eng.num_slots):
            self.eng.clear_slot(slot)
        return self.eng

    def serial(self, prompt, n_new, slot=0):
        """The serial loop: the request alone in the engine, each pass
        fetched and booked (``step()``) before the next is dispatched ->
        (its tokens, its commits)."""
        eng = self.cleared()
        _commit(eng, slot, prompt, _blocks(eng, slot))
        got = _Taken(prompt, n_new)
        while not got.take(eng.step([slot]), slot):
            pass
        return got.tokens(), got.commits


@pytest.fixture(scope="module")
def block():
    return _Block()


@pytest.mark.parametrize("seed", range(6))
def test_a_pass_books_itself_as_the_host_books_it(block, seed):
    """Random blocks, masks, pass counts and cursors; two passes over random
    sets of slots, the second queued before the first is fetched and made to
    take every row from the device (``_fresh`` cleared by hand, which the
    lane never does: it reads the host's row of a slot that a pass left out).
    What the second pass leaves on the device is, row for row, what
    ``_book_block`` has made of the host's mirrors by then, and what the
    arithmetic written out slot by slot makes of the passes' own outputs: a
    block without masks stored, any other a pass on, a row that a pass did not
    step as it was."""
    eng = block.cleared()
    rs = np.random.RandomState(seed)
    n = eng.num_slots
    masked = rs.rand(n, BL) < 0.5
    masked[rs.randint(n)] = False          # a block whose next pass stores it
    with eng.dev_lock:
        for s in range(n):
            eng._tables[s] = _blocks(eng, s)
        eng._blk_masked[:] = masked
        eng._blk_ids[:] = np.where(masked, MASK, rs.randint(1, 250, (n, BL)))
        eng._blk_step[:] = rs.randint(0, 4, n)
        eng._pos[:] = rs.randint(0, 12, n) * BL
        eng._fresh[:] = True

    def mirrors():
        return eng._blk_ids, eng._blk_masked, eng._blk_step, eng._pos

    ids, masks, count, pos = want = [a.copy() for a in mirrors()]
    # one slot is in neither pass, and no pass is empty
    rest = np.delete(np.arange(n), rs.randint(n))
    sets = [np.union1d(rest[rs.rand(n - 1) < 0.6], rest[rs.randint(n - 1)])
            for _ in range(2)]
    first = eng.dispatch_step(sets[0])
    eng._fresh[:] = False
    second = eng.dispatch_step(sets[1])
    assert second.ahead and not first.ahead
    for step in (first, second):
        tick = eng.fetch_step(step)
        assert tick.live.nonzero()[0].tolist() == step.active.tolist()
        for s in step.active:
            if not masks[s].any():
                assert tick.stored[s] and not tick.commit[s].any()
                pos[s] += BL
                ids[s], masks[s], count[s] = MASK, True, 0
            else:
                assert tick.commit[s].any() and not tick.stored[s]
                assert not (tick.commit[s] & ~masks[s]).any()
                ids[s] = tick.ids[s]
                masks[s] &= ~tick.commit[s]
                count[s] += 1
    for dev, host, by_hand in zip(eng._blk_dev, mirrors(), want):
        dev = np.asarray(dev)
        assert dev.dtype == host.dtype == by_hand.dtype
        assert np.array_equal(dev, host) and np.array_equal(host, by_hand)


def test_block_passes_run_ahead_equal_the_serial_loop(block):
    """Slot 0 decodes throughout.  Slot 1's request ends inside a block with
    pass K: pass K+1, queued before pass K was fetched, carries the slot all
    the same, a row computed and booked for nobody.  The slot is cleared as
    pass K is booked and ITS BLOCKS go straight to the next prompt, whose
    commit is queued behind pass K+1 and whose first pass, K+2, takes the
    host's row, queued before pass K+1 is fetched.  Every token and every
    commit ``(position, token, pass)`` is the serial loop's."""
    prompts = dict(zip("abc", _prompts(61, (9, 6, 13))))
    n_new = {"a": 17, "b": 3, "c": 6}
    eng = block.cleared()
    taken = {k: _Taken(prompts[k], n_new[k]) for k in prompts}
    owner, flights, dead = {0: "a", 1: "b"}, [], []
    for slot, k in owner.items():
        _commit(eng, slot, prompts[k], _blocks(eng, slot))

    def queue():
        flights.append((eng.dispatch_step(sorted(owner)), dict(owner)))

    def book():
        step, owned = flights.pop(0)
        tick = eng.fetch_step(step)
        for slot, k in owned.items():
            if not tick.live[slot]:
                dead.append((step.seq, k))
                assert not tick.commit[slot].any() and not tick.stored[slot]
            elif taken[k].take(tick, slot):
                del owner[slot]
                eng.clear_slot(slot)        # released as the pass is booked
                if k == "b":                # ... its blocks handed on at once
                    assert flights[0][1][slot] == "b"
                    _commit(eng, slot, prompts["c"], _blocks(eng, slot))
                    owner[slot] = "c"

    queue()
    while flights:
        if owner:
            queue()
            assert len(flights) == 1 or flights[-1][0].ahead
        book()
    assert eng.step_in_flight is None
    # each request's last pass was followed by one queued before it was
    # fetched, which carried its slot for nobody
    assert sorted(k for _seq, k in dead) == ["a", "b", "c"]
    for k, slot in (("a", 0), ("b", 1), ("c", 1)):
        tokens, commits = block.serial(prompts[k], n_new[k], slot=slot)
        assert taken[k].tokens() == tokens, k
        assert taken[k].commits == commits, k


def _serve_blocks(block, jobs, since=None, **cfg):
    """``jobs`` ((prompt, n_new), ...) through a server of the tiny block
    decoder -> (requests, outputs, ``decode.tick`` records, stats), each
    output and each ``req.commits`` held to the serial loop's."""
    since = time.perf_counter() if since is None else since
    with _server(block.net, **cfg) as srv:
        futs = [srv.submit(p, max_new_tokens=n) for p, n in jobs]
        outs = [f.result(180) for f in futs]
        rep = srv.replicas[0]
        for _ in range(20000):       # a last pass, for nobody, has its record
            if tracing.lane_log("decode.tick", since=since)[-1]["seq"] \
                    == srv.engine.steps and rep.decode._flight is None:
                break
            time.sleep(0.001)
        assert rep.mgr.check()
        stats = srv.stats()
        assert srv.engine._step._cache_size() == 1
    reqs = [f.request for f in futs]
    for (p, n), req, out in zip(jobs, reqs, outs):
        tokens, commits = block.serial(p, n, slot=req.slot)
        assert out[:len(p)].tolist() == list(p)
        assert out[len(p):].tolist() == tokens
        assert req.commits == commits
    assert stats["failed"] == 0 and stats["completed"] == len(jobs)
    return reqs, outs, tracing.lane_log("decode.tick", since=since), stats


SERVED = {
    # six tokens behind a prompt of 7 end at position 12, a block's first;
    # three behind 5 at position 7, a block's last
    "ends_inside_a_block": (((7, 6), (5, 3), (10, 1)), {}),
    # every remainder of the prompt over the block length
    "the_first_block_holds_the_rest_of_the_prompt":
        (((1, 5), (3, 7), (6, 4), (9, 6), (14, 9)), {}),
    # a pool of 12 blocks of 4 under maxima of 8 to 11: slots are parked
    "parked_by_a_pool_under_parity":
        (((1, 30), (3, 28), (8, 35), (14, 26), (17, 20)),
         {"num_blocks": 12}),
    # one slot: each request takes over the slot and the blocks of the last
    "a_freed_slot_readmitted": (((11, 5), (4, 9), (7, 2)), {"num_slots": 1}),
}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_block_tokens_and_commits_are_the_serial_loops(block, case):
    sizes, cfg = SERVED[case]
    jobs = [(p, n) for p, (_len, n) in zip(
        _prompts(70, [size for size, _n in sizes]), sizes)]
    _reqs, _outs, ticks, stats = _serve_blocks(block, jobs, **cfg)
    assert stats["decode_steps"] == len(ticks)
    # a pass queued before the one ahead of it was fetched may carry slots
    # whose requests that one ended: left out of what the record counts
    assert all(t["rows"] == t["n_active"] * BL == len(t["request_ids"]) * BL
               for t in ticks)
    assert any(t["ahead"] for t in ticks)
    assert sum(t["n_finished"] for t in ticks) == len(jobs)
    assert stats["blocks"] == {
        "block_passes": sum(t["block_passes"] for t in ticks),
        "blocks_committed": sum(t["n_store"] for t in ticks),
        "committed_tokens": sum(t["committed"] for t in ticks)}
    if case == "parked_by_a_pool_under_parity":
        assert sum(t["n_parked"] for t in ticks) > 0


def test_a_hand_off_adopted_while_a_pass_is_in_flight(block, monkeypatch):
    """A decodes; the lane is held in a fetch with the pass after queued
    behind it; B is prefilled and committed behind that pass, adopted by the
    next turn and carried, from the host's row, by the pass that turn
    queues."""
    import threading

    hold, held = threading.Event(), threading.Event()
    real_fetch = generative._materialize

    def gated_fetch(arrays):
        if hold.is_set():
            held.set()
            while hold.is_set():
                time.sleep(0.001)
        return real_fetch(arrays)

    monkeypatch.setattr(generative, "_materialize", gated_fetch)
    a, b = _prompts(71, (9, 6))
    since = time.perf_counter()
    try:
        with _server(block.net, num_slots=2) as srv:
            lane = srv.replicas[0].decode
            fa = srv.submit(a, max_new_tokens=40)
            while fa.request.first_tick is None:
                time.sleep(0.001)
            hold.set()
            assert held.wait(60) and lane._flight is not None
            queued = lane._flight.step.seq
            assert srv.engine.step_in_flight == queued
            fb = srv.submit(b, max_new_tokens=7)
            while fb.request.t_commit is None:
                time.sleep(0.001)
            hold.clear()
            outs = [f.result(180) for f in (fa, fb)]
    finally:
        hold.clear()
    for p, n, f, out in ((a, 40, fa, outs[0]), (b, 7, fb, outs[1])):
        tokens, commits = block.serial(p, n, slot=f.request.slot)
        assert out[len(p):].tolist() == tokens
        assert f.request.commits == commits
    turn, = [t for t in tracing.lane_log("slot.turn", since=since)
             if t["request_id"] == fb.request.id]
    ticks = {t["seq"]: t for t in tracing.lane_log("decode.tick", since=since)}
    assert turn["tick"] > queued and ticks[queued]["ahead"]
    assert ticks[queued]["request_ids"] == (fa.request.id,)
    assert (ticks[turn["tick"]]["n_adopted"],
            ticks[turn["tick"]]["n_active"]) == (1, 2)


def test_block_ticks_say_ahead_and_leave_the_dead_pass_out(block, monkeypatch):
    """A lone request: nothing covers, so every pass but the first is queued
    before the one ahead of it is fetched, the one after its last too, which
    carries its slot for nobody: a record of no row.  The step's own dispatch
    lies a turn before the turn's, and the counters and the summary count the
    passes queued ahead."""
    from mxnet_tpu import telemetry

    seen = []
    monkeypatch.setattr(telemetry, "count",
                        lambda name, n=1: seen.append((name, n)))
    (p,) = _prompts(72, (6,))
    reqs, _outs, ticks, stats = _serve_blocks(block, [(p, 6)])
    assert [t["ahead"] for t in ticks] == [False] + [True] * (len(ticks) - 1)
    assert [t["seq"] for t in ticks] == list(range(1, len(ticks) + 1))
    *live, dead = ticks
    assert all(t["request_ids"] == (reqs[0].id,) and t["rows"] == BL
               for t in live)
    assert (live[-1]["n_finished"], dead["n_finished"]) == (1, 0)
    assert (dead["n_active"], dead["rows"], dead["n_store"], dead["committed"],
            dead["block_passes"], dead["request_ids"]) == (0, 0, 0, 0, 0, ())
    for a, b in zip(ticks, ticks[1:]):
        assert b["t_step_disp1"] < a["t_tok"]
        assert b["t_step_disp1"] <= b["t_disp0"] <= b["t_disp1"] <= b["t_tok"]
    # 6 tokens behind a prompt of 6: one commit a pass on seeded weights
    assert sum(t["committed"] for t in live) >= 6
    assert sum(t["n_store"] for t in live) >= 1
    steps = sum(n for name, n in seen if name == "serving.decode.steps")
    ahead = sum(n for name, n in seen if name == "serving.decode.steps_ahead")
    assert (steps, ahead) == (len(ticks), len(ticks) - 1)
    assert stats["decode_steps_ahead"] == ahead


def test_a_covered_block_turn_queues_nothing(block, monkeypatch):
    """Where the prefill lane has the device behind the pass in flight the
    turn queues nothing: no pass runs ahead, none is made for nobody, and
    the tokens are the same."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.lanes import DecodeLane

    monkeypatch.setattr(DecodeLane, "_prefill_covers", lambda self, step: True)
    emitted = []
    jobs = list(zip(_prompts(73, (5, 10)), (6, 4)))
    since = time.perf_counter()
    with _server(block.net) as srv:
        for p, n in jobs:
            srv.generate(p, max_new_tokens=n)
        monkeypatch.setattr(telemetry, "emit", emitted.append)
        srv.replicas[0].emit_summary()
        assert srv.stats()["decode_steps_ahead"] == 0
    ticks = tracing.lane_log("decode.tick", since=since)
    assert ticks and not any(t["ahead"] for t in ticks)
    assert all(t["n_active"] == 1 for t in ticks)
    assert emitted[-1]["steps_ahead_share"] == 0.0
    _reqs, _outs, ahead, _stats = _serve_blocks(block, jobs[:1])
    assert [t["committed"] for t in ticks[:len(ahead) - 1]] \
        == [t["committed"] for t in ahead[:-1]]


@pytest.mark.parametrize("half", ["dispatch", "fetch"])
def test_an_exception_in_either_half_of_a_block_pass(block, half, monkeypatch):
    """Two passes may be in flight when a half raises: each request fails
    once, every slot and block comes back, no pass is left in flight and no
    slot's next pass reads what the dropped ones left on the device; the
    server goes on serving the serial loop's tokens."""
    srv = _server(block.net, num_slots=2)
    eng, rep = srv.engine, srv.replicas[0]
    prompts = _prompts(74, (6, 9, 5))
    real_fetch, real_step = generative._materialize, eng._step
    armed = {"on": False}

    def planted(real, what):
        def call(*args):
            if armed["on"]:
                armed["on"] = False
                raise RuntimeError("planted: the %s half" % what)
            return real(*args)
        return call

    # the decode lane holds still, outside the device lock, until BOTH
    # prefills are committed: neither request has a pass before the other
    # is there to be adopted, so the first cannot run out its 40 tokens
    # (ten blocks of passes) before the second decodes, whatever the
    # worker's load, and the fault is armed with both in flight
    both_committed = threading.Event()
    real_dispatch = eng.dispatch_step

    def held(active):
        assert both_committed.wait(120)
        return real_dispatch(active)

    eng.dispatch_step = held
    with srv:
        if half == "fetch":
            monkeypatch.setattr(generative, "_materialize",
                                planted(real_fetch, half))
        else:
            eng._step = planted(real_step, half)
        lost = [srv.submit(p, max_new_tokens=40) for p in prompts[:2]]
        while any(f.request.t_commit is None for f in lost):
            time.sleep(0.001)
        both_committed.set()
        while any(f.request.first_tick is None for f in lost):
            time.sleep(0.001)         # both decode: two passes in flight
        assert not any(f.done() for f in lost)
        armed["on"] = True
        for f in lost:
            with pytest.raises(RuntimeError, match="planted"):
                f.result(120)
        monkeypatch.setattr(generative, "_materialize", real_fetch)
        eng._step = real_step
        for _ in range(5000):         # the count follows the future
            if rep.failed == 2:
                break
            time.sleep(0.001)
        assert rep.failed == 2 and srv.stats()["failed"] == 2
        assert eng.step_in_flight is None and rep.decode._flight is None
        assert eng._fresh.all()
        assert rep.mgr.free_slots() == 2
        assert rep.mgr.allocator.blocks_in_use == 0
        kept = srv.submit(prompts[2], max_new_tokens=5)
        out = kept.result(120)
        assert rep.failed == 2 and rep.completed == 1
    tokens, commits = block.serial(prompts[2], 5, slot=kept.request.slot)
    assert out[len(prompts[2]):].tolist() == tokens
    assert kept.request.commits == commits
    assert rep.mgr.check()


def test_the_carried_blocks_add_no_compile_after_warm_up(block):
    """The first pass takes zeros committed as a pass's output is, so the
    second is the first's compiled program; requests of warmed buckets then
    compile nothing."""
    with _server(block.net) as srv:
        eng = srv.engine
        for p in _prompts(75, (6, 12)):         # buckets 8 and 16
            srv.generate(p, max_new_tokens=2)
        warm = eng.compiled_signatures()
        assert ("step",) in warm and eng._step._cache_size() == 1
        futs = [srv.submit(p, max_new_tokens=n) for p, n in
                zip(_prompts(76, (5, 9, 13, 7)), (6, 9, 3, 11))]
        for f in futs:
            f.result(180)
        assert eng.compiled_signatures() == warm
        assert eng._step._cache_size() == 1

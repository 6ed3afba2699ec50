"""Serving subsystem: bucketing math, KV-slot invariants, backpressure,
and the continuous-batching acceptance paths (multi-client bit-identity
under <=4 compiled signatures; a late generative request joining an
in-flight decode batch)."""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, serialization, serving, telemetry
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serving import (BucketPolicy, RequestQueue,
                               ServerConfig, ServerOverloadedError,
                               pad_batch, pow2_bucket)
from mxnet_tpu.serving.protocol import Request, ServerClosedError
from mxnet_tpu.telemetry.sinks import ListSink


# --- bucketing math ----------------------------------------------------------

def test_pow2_bucket_selection():
    assert pow2_bucket(1, 1, 64) == 1
    assert pow2_bucket(3, 1, 64) == 4
    assert pow2_bucket(4, 1, 64) == 4
    assert pow2_bucket(5, 1, 64) == 8
    assert pow2_bucket(33, 1, 64) == 64
    assert pow2_bucket(2, 8, 64) == 8      # clamped to the floor
    with pytest.raises(mx.MXNetError):
        pow2_bucket(65, 1, 64)             # over the ceiling rejects


def test_bucket_policy_signature_space():
    p = BucketPolicy(max_batch=4, max_length=64, min_batch=1, min_length=8)
    assert p.batch_buckets() == [1, 2, 4]
    assert p.length_buckets() == [8, 16, 32, 64]
    assert len(p.signatures()) == 12
    assert p.batch_bucket(3) == 4
    assert p.length_bucket(17) == 32
    # every bucketed shape is a member of the enumerated space
    for n in range(1, 5):
        for l in range(1, 65):
            assert (p.batch_bucket(n), p.length_bucket(l)) \
                in p.signatures()


def test_pad_batch_shapes_and_errors():
    exs = [np.ones((3, 5)), 2 * np.ones((7, 5))]
    b = pad_batch(exs, 4, 8)
    assert b.shape == (4, 8, 5)
    assert np.array_equal(b[0, :3], exs[0])
    assert np.array_equal(b[1, :7], exs[1])
    assert (b[0, 3:] == 0).all()           # length padding is zeros
    assert np.array_equal(b[2], b[0])      # vacant rows repeat row 0
    with pytest.raises(mx.MXNetError):
        pad_batch(exs, 1, 8)               # too many examples
    with pytest.raises(mx.MXNetError):
        pad_batch(exs, 4, 4)               # length over bucket
    with pytest.raises(mx.MXNetError):
        pad_batch([], 4, 8)


# --- a shape-polymorphic position-wise model for bit-identity tests ----------

def _eighths(rs, *shape):
    """Seeded float32 values on a grid of 1/8: every product and every
    six-term sum of the model below is exact in float32, so its result
    does not depend on the order or the fusing (FMA or not) that the
    gemm kernel picked for a batch shape — which differs between CPUs,
    and made the bit-identity tests pass on one machine and fail on the
    next.  What they hold still fails loudly: a row demuxed from the
    wrong slot or padding that leaks into a real row is off by far more
    than a rounding."""
    return (np.round(rs.randn(*shape) * 8) / 8).astype(np.float32)


def _positionwise_predictor(tmp_path, in_dim=6, hidden=5):
    """nnvm FullyConnected(flatten=False) chain: every (batch, length)
    row is an independent gemm row, so padded forwards are bit-identical
    to unpadded ones on the real rows (weights and inputs from
    :func:`_eighths`, so that rounding cannot differ either)."""
    import mxnet_tpu.symbol as sym

    data = sym.Variable("data")
    w = sym.Variable("fc_weight")
    b = sym.Variable("fc_bias")
    out = sym.FullyConnected(data, w, b, num_hidden=hidden, flatten=False,
                             name="fc")
    out = sym.Activation(out, act_type="relu")
    rs = np.random.RandomState(7)
    wv = _eighths(rs, hidden, in_dim)
    bv = _eighths(rs, hidden)
    prefix = str(tmp_path / "posw")
    out.save(f"{prefix}-symbol.json")
    serialization.save_ndarrays(f"{prefix}-0000.params", {
        "arg:fc_weight": nd.array(wv), "arg:fc_bias": nd.array(bv)})
    pred = Predictor(f"{prefix}-symbol.json", f"{prefix}-0000.params")
    oracle = lambda x: np.maximum(x @ wv.T + bv, 0.0)  # noqa: E731
    return pred, oracle


def test_padding_bit_identity_vs_unpadded_oracle(tmp_path):
    """The demuxed rows of a padded, bucketed batch forward are
    BIT-identical to each request's own unbatched forward."""
    pred, _ = _positionwise_predictor(tmp_path)
    rs = np.random.RandomState(3)
    exs = [_eighths(rs, l, 6) for l in (3, 7, 5)]
    batch = pad_batch(exs, 4, 8)
    padded = pred.predict(batch).asnumpy()
    for i, x in enumerate(exs):
        solo = pred.predict(x[None]).asnumpy()[0]
        assert np.array_equal(padded[i, :len(x)], solo)


# --- backpressure ------------------------------------------------------------

def test_bounded_queue_backpressure():
    q = RequestQueue(capacity=2)
    q.put(Request(inputs={}, length=1))
    q.put(Request(inputs={}, length=1))
    with pytest.raises(ServerOverloadedError):
        q.put(Request(inputs={}, length=1))
    assert q.rejected == 1
    q.close()
    with pytest.raises(ServerClosedError):
        q.put(Request(inputs={}, length=1))


def test_submit_requires_running_server(tmp_path):
    pred, _ = _positionwise_predictor(tmp_path)
    srv = serving.InferenceServer(pred, ServerConfig(max_batch=2))
    with pytest.raises(ServerClosedError):
        srv.submit(np.zeros((4, 6), np.float32))


# --- telemetry rolling histograms -------------------------------------------

def test_telemetry_rolling_histogram():
    telemetry.enable(memory=False, cost=False)
    try:
        for v in range(1, 101):
            telemetry.hist("t.lat", float(v), cap=10)
        s = telemetry.hist_summary("t.lat")
        # window keeps only the last 10 of 100 observations
        assert s["count"] == 100 and s["window"] == 10
        assert s["p50"] == 95.0 and s["p99"] == 100.0
        assert s["min"] == 1.0 and s["max"] == 100.0
        assert "t.lat" in telemetry.hists()
        assert telemetry.hist_summary("absent") is None
    finally:
        telemetry.disable()
    # disabled -> no-op, no state
    telemetry.hist("t.off", 1.0)
    assert telemetry.hist_summary("t.off") is None


def test_telemetry_emit_to_sinks():
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    try:
        rec = telemetry.emit({"record": "x", "v": 1})
        assert rec == {"record": "x", "v": 1}
        assert sink.records == [rec]
    finally:
        telemetry.disable()
    assert telemetry.emit({"record": "y"}) is None


# --- the acceptance paths ----------------------------------------------------

def test_multi_client_continuous_batching_end_to_end(tmp_path):
    """Concurrent mixed-length clients; <=4 compiled signatures
    (predictor cache stats), bit-identical results, per-request JSONL
    records and a rolling serving.latency summary."""
    pred, oracle = _positionwise_predictor(tmp_path)
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    cfg = ServerConfig(max_batch=4, max_length=16, min_batch=2,
                       min_length=8, output_length_axis=0,
                       batch_window_ms=10.0, summary_every=4)
    srv = serving.InferenceServer(pred, cfg)
    rs = np.random.RandomState(11)
    lengths = [3, 5, 9, 7, 12, 4, 8, 15, 2, 6, 11, 16]
    inputs = [_eighths(rs, l, 6) for l in lengths]
    results = [None] * len(inputs)

    def client(i):
        results[i] = srv.infer(inputs[i], timeout=60.0)

    try:
        with srv:
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(inputs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        stats = srv.stats()
    finally:
        telemetry.disable()

    # bit-identity: every demuxed result equals the unbatched oracle
    for x, got in zip(inputs, results):
        assert got.shape == (len(x), 5)
        assert np.array_equal(got, oracle(x))
    # bucketing held: two length buckets x two batch buckets at most
    assert 1 <= stats["cache"]["signatures"] <= 4
    assert stats["cache"]["misses"] == stats["cache"]["signatures"]
    assert stats["completed"] == len(inputs)
    # dynamic batching actually batched (not all head-of-line singletons)
    assert stats["batches"] < len(inputs)
    # JSONL stream: per-request records with the span fields
    recs = [r for r in sink.records if r.get("record") == "serving.request"]
    assert len(recs) == len(inputs)
    for r in recs:
        assert r["queue_wait_ms"] >= 0.0
        assert r["total_ms"] > 0.0
        assert r["batch_size"] >= 1
        assert tuple(r["bucket"]) in {(b, l) for b, l
                                      in cfg.policy.signatures()}
    assert any(r["batch_size"] > 1 for r in recs)
    # rolling latency summary landed with percentiles
    sums = [r for r in sink.records if r.get("record") == "serving.latency"]
    assert sums
    last = sums[-1]
    assert last["total_ms"]["p50"] <= last["total_ms"]["p99"]
    assert last["batch_size"]["max"] > 1


def test_generative_paged_lanes_late_join_and_parity():
    """The default (paged KV + disaggregated lanes) path: a late
    request is prefilled by the prefill lane and handed off to the
    decode lane WITHOUT stalling the in-flight decode, both results
    are token-exact vs offline generate(), and the request records
    carry the lane fields (replica / kv_blocks / handoff_ms)."""
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    telemetry.enable(memory=False, cost=False)
    sink = ListSink()
    telemetry.add_sink(sink)
    rs = np.random.RandomState(0)
    p1 = rs.randint(1, 250, size=5)
    p2 = rs.randint(1, 250, size=9)
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, summary_every=2)
    srv = serving.GenerativeServer(net, cfg)
    try:
        with srv:
            # warm both prefill buckets so the late join below isn't
            # skewed by first-compile time (decode does NOT stall for
            # prefill in the lanes path — that's the point of it)
            srv.generate(p1, max_new_tokens=2)
            srv.generate(p2, max_new_tokens=2)
            base = srv.engine.steps
            f1 = srv.submit(p1, max_new_tokens=40)
            deadline = time.time() + 60
            while srv.engine.steps < base + 2 and time.time() < deadline:
                time.sleep(0.01)
            assert srv.engine.steps >= base + 2
            f2 = srv.submit(p2, max_new_tokens=4)
            r1 = f1.result(120)
            r2 = f2.result(120)
        stats = srv.stats()
    finally:
        telemetry.disable()

    o1 = net.generate(nd.array(p1[None]), 40).asnumpy()[0]
    o2 = net.generate(nd.array(p2[None]), 4).asnumpy()[0]
    assert np.array_equal(r1, o1)
    assert np.array_equal(r2, o2)

    recs = [r for r in sink.records if r.get("record") == "serving.request"]
    assert len(recs) == 4
    r1rec, r2rec = recs[-2], recs[-1]
    if r1rec["request_id"] > r2rec["request_id"]:
        r1rec, r2rec = r2rec, r1rec
    # the late request joined mid-flight and (its prefill being warm)
    # finished its 4 tokens long before the 40-token request
    assert r2rec["joined_step"] >= base + 2
    assert r2rec["done_step"] < r1rec["done_step"]
    assert r1rec["ttft_ms"] > 0 and r2rec["ttft_ms"] > 0
    # lane fields: served by replica 0, the request's claim on the pool
    # (5+40 tokens -> 3 blocks of 16), handoff measured
    for rec in (r1rec, r2rec):
        assert rec["replica"] == 0
        assert rec["lane"] == "decode"
        assert rec["handoff_ms"] >= 0
    assert r1rec["kv_blocks"] == 3
    assert r2rec["kv_blocks"] == 1
    # slots shared concurrently; pool fully returned at drain
    assert stats["kv_cache"]["peak_occupancy"] == 2
    assert stats["kv_cache"]["occupancy"] == 0
    assert stats["kv_cache"]["blocks_in_use"] == 0
    # blocks are granted as a request grows: the long one's third comes
    # after the short one has left with its one
    assert stats["kv_cache"]["peak_blocks_in_use"] == 3
    assert stats["kv_cache"]["grants"] == 2
    assert stats["kv_cache"]["parked_slot_ticks"] == 0
    # ONE decode-step signature for the server lifetime, prefill per
    # prompt bucket
    sigs = stats["compiled_signatures"]
    assert sigs.count(("step",)) == 1
    assert len([s for s in sigs if s[0] == "prefill"]) <= 2
    # rolling summary carries the handoff percentiles
    sums = [r for r in sink.records if r.get("record") == "serving.latency"]
    assert sums and sums[-1]["handoff_ms"] is not None
    assert sums[-1]["kv_cache"]["block_size"] == 16


def test_generative_int8_load_option():
    """int8 weight quantization at load time: the engine decodes and
    honors shapes (no parity claim vs fp32)."""
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    rs = np.random.RandomState(1)
    prompt = rs.randint(1, 250, size=6)
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, int8=True)
    srv = serving.GenerativeServer(net, cfg)
    assert srv.engine.int8
    # weights really are int8 on device
    q = srv.engine._w["layers"][0]["q"]
    assert str(q["q8"].dtype) == "int8"
    with srv:
        out = srv.generate(prompt, max_new_tokens=5)
    assert out.shape == (len(prompt) + 5,)
    assert np.array_equal(out[:len(prompt)], prompt)
    assert (out < net.config.vocab_size).all()


# --- paged KV: block allocator + manager invariants --------------------------

def test_block_allocator_invariants():
    """All-or-nothing allocation, no double-assignment, double-free
    raises, and a full alloc/free round-trip restores the pool."""
    from mxnet_tpu.serving import BlockAllocator

    a = BlockAllocator(num_blocks=6, block_size=16)
    assert a.free_blocks == 6 and a.blocks_in_use == 0
    b1 = a.alloc(4)
    b2 = a.alloc(2)
    assert len(b1) == 4 and len(b2) == 2
    # no block handed out twice
    assert len(set(b1) | set(b2)) == 6
    assert a.free_blocks == 0 and a.blocks_in_use == 6
    # all-or-nothing: an empty pool refuses, state unchanged
    assert a.alloc(1) is None
    assert a.free_blocks == 0
    a.free(b2)
    assert a.free_blocks == 2 and a.peak_blocks_in_use == 6
    with pytest.raises(mx.MXNetError):
        a.free(b2)                         # double-free
    a.free(b1)
    assert a.free_blocks == 6 and a.blocks_in_use == 0
    a.check()
    # round-trip: the pool serves the full count again
    assert len(a.alloc(6)) == 6


def test_paged_manager_admit_advance_evict():
    """A claim sized by prompt+budget, the prompt's blocks held at
    admission; advancing past the budget raises; eviction returns every
    block."""
    from mxnet_tpu.serving import PagedKVCacheManager

    mgr = PagedKVCacheManager(num_slots=2, max_len=64, num_blocks=8,
                              block_size=16)
    assert mgr.blocks_for(9, 4) == 1       # 13 tokens -> 1 block
    assert mgr.blocks_for(9, 8) == 2       # 17 tokens -> 2 blocks
    slot, blocks = mgr.admit("r1", 17, 15)  # 32 tokens: 2 held, 2 at most
    assert len(blocks) == 2
    assert mgr.allocator.blocks_in_use == 2
    for _ in range(15):
        assert mgr.grant_step([slot]) == ({}, [])
        mgr.advance(slot)
    with pytest.raises(mx.MXNetError):
        mgr.advance(slot)                  # past the 32-token budget
    mgr.evict(slot)
    assert mgr.allocator.blocks_in_use == 0
    mgr.check()
    st = mgr.stats()
    assert st["capacity_tokens"] == 8 * 16
    assert st["peak_tokens"] >= 17
    assert st["tokens_in_flight"] == 0


# --- blocks granted as a request grows: a pool that parks -----------------------

def _through_a_pool(net, prompts, max_new, num_blocks, lead=0, **cfg):
    """The requests at once (the first ``lead`` a few steps ahead, so that a
    prefix cache holds their prompt) through a server with ``num_blocks`` ->
    (results, the run's ``decode.tick`` records, ``server.stats()``)."""
    from mxnet_tpu.telemetry import tracing

    since = time.perf_counter()
    srv = serving.GenerativeServer(net, ServerConfig(
        max_batch=2, max_length=64, min_length=8, summary_every=1 << 30,
        num_blocks=num_blocks, **cfg))
    with srv:
        futs = [srv.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[:lead], max_new)]
        deadline = time.time() + 60
        while lead and srv.engine.steps < 3 and time.time() < deadline:
            time.sleep(0.01)
        futs += [srv.submit(p, max_new_tokens=n)
                 for p, n in zip(prompts[lead:], max_new[lead:])]
        outs = [f.result(120) for f in futs]
        srv.replicas[0].mgr.check()
        stats = srv.stats()
    return outs, tracing.lane_log("decode.tick", since=since), stats


@pytest.mark.parametrize("tick", ["ahead", "speculative", "radix"])
def test_a_pool_that_parks_gives_the_tokens_of_one_that_never_does(tick):
    """The same requests, greedy, through a pool at parity (slots x
    max_blocks: every remaining need fits at once, the rule's one comparison)
    and through one a little over the largest request's maximum: the same
    tokens request by request, all finish, nothing is evicted; the small
    pool's lane log counts parked slots and the large one's none.  The
    token-at-a-time tick running a step ahead, the speculative tick (a draft
    that the target rejects, so windows roll back and give blocks back) and a
    prompt prefix shared through the radix cache."""
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    rs = np.random.RandomState(7)
    lens, max_new, lead = (5, 9, 7, 6, 11), [40, 30, 44, 35, 25], 0
    cfg = dict(num_slots=3, block_size=4)
    tight = 16                  # the largest maximum is 13 blocks; parity 48
    if tick == "speculative":
        draft = llama_tiny()
        draft.initialize()      # other weights: the target rejects
        cfg.update(draft_net=draft, spec_k=3)
    prompts = [rs.randint(1, 250, size=n) for n in lens]
    if tick == "radix":
        system = rs.randint(1, 250, size=20)
        prompts = [np.concatenate([system, p]) for p in prompts]
        max_new, lead = [30, 24, 26, 28, 20], 1
        cfg.update(block_size=8, radix_cache=True)
        tight = 10              # maxima of 7 and 8 blocks of 8; parity 24
    wide, ticks_w, stats_w = _through_a_pool(net, prompts, max_new, None,
                                             lead, **cfg)
    small, ticks_s, stats_s = _through_a_pool(net, prompts, max_new, tight,
                                              lead, **cfg)
    for p, n, a, b in zip(prompts, max_new, wide, small):
        assert len(a) == len(p) + n
        assert np.array_equal(a, b)
    o = net.generate(nd.array(prompts[2][None]), max_new[2]).asnumpy()[0]
    assert np.array_equal(small[2], o)
    assert ticks_w and all(t["n_parked"] == 0 for t in ticks_w)
    assert sum(t["n_parked"] for t in ticks_s) > 0
    assert all(t["n_active"] >= 1 for t in ticks_s)
    kv_w, kv_s = stats_w["kv_cache"], stats_s["kv_cache"]
    assert kv_w["parked_slot_ticks"] == 0
    assert kv_w["unsafe_refusals"] == {"admit": 0, "grant": 0}
    assert kv_s["parked_slot_ticks"] >= sum(t["n_parked"] for t in ticks_s)
    assert kv_s["unsafe_refusals"]["grant"] == kv_s["parked_slot_ticks"]
    assert kv_s["peak_blocks_in_use"] <= tight
    for kv in (kv_w, kv_s):
        # every request admitted once and finished: nothing evicted for room
        assert kv["admits"] == kv["evictions"] == len(prompts)
        assert kv["grants"] > 0 and kv["occupancy"] == 0
    assert stats_s["lanes"][0]["parked_slot_ticks"] \
        == kv_s["parked_slot_ticks"]
    assert stats_s["completed"] == len(prompts) and stats_s["failed"] == 0
    if tick == "ahead":
        assert any(t["ahead"] for t in ticks_s)
    elif tick == "speculative":
        assert stats_s["speculative"]["draft_tokens"] \
            > stats_s["speculative"]["accepted_tokens"]
    else:
        assert stats_s["radix_cache"]["hits"] >= 3
        assert kv_s["peak_shared_blocks"] >= 2


def test_paged_capacity_beats_ledger():
    """The acceptance mix: a pool whose worst-case ``slots × max_len``
    exceeds its token capacity still admits (and correctly serves) all
    four short requests, where a fixed ``max_len`` row a slot would
    hold two."""
    from mxnet_tpu.models.llama import llama_tiny
    from mxnet_tpu.serving import PagedKVCacheManager

    # manager level: 8 blocks × 16 = 128 tokens backs FOUR slots whose
    # worst case is 4 × 64 = 256
    mgr = PagedKVCacheManager(num_slots=4, max_len=64, num_blocks=8,
                              block_size=16)
    admits = [mgr.admit(i, 9, 4) for i in range(4)]   # 13 tokens each
    assert all(a is not None for a in admits)
    assert mgr.stats()["occupancy"] == 4
    for slot, _ in admits:
        mgr.evict(slot)
    assert mgr.allocator.free_blocks == 8
    mgr.check()

    # server level: the undersized pool serves the same mix token-exact
    # vs offline generate
    net = llama_tiny()
    net.initialize()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 250, size=9) for _ in range(4)]
    want = [net.generate(nd.array(p[None]), 4).asnumpy()[0] for p in prompts]
    cfg = ServerConfig(max_batch=4, max_length=64, min_length=8,
                       num_slots=4, num_blocks=8, block_size=16)
    srv = serving.GenerativeServer(net, cfg)
    assert srv.engine.num_blocks == 8
    with srv:
        futs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        got = [f.result(120) for f in futs]
        stats = srv.stats()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    kv = stats["kv_cache"]
    assert kv["capacity_tokens"] == 128        # < 4 slots × 64 worst case
    assert kv["admits"] == 4
    assert kv["peak_occupancy"] >= 3           # served concurrently
    assert kv["blocks_in_use"] == 0 and kv["occupancy"] == 0


def test_generative_server_mesh_dp2_tp2_token_exact():
    """dp2×tp2 CPU mesh: weights tensor-parallel per replica, two
    independent replicas behind one queue.  Token-exact vs
    single-device offline generate, ONE decode compile per replica, both
    replicas take work, and the engine's pool bytes match the memory
    planner's ``plan_kv_pool`` on the tp submesh."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu.memory import plan_kv_pool
    from mxnet_tpu.models.llama import llama_tiny

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (dp2×tp2)")
    net = llama_tiny()
    net.initialize()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 250, size=n) for n in (5, 9, 12, 7)]
    want = [net.generate(nd.array(p[None]), 6).asnumpy()[0] for p in prompts]

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2, summary_every=4)
    srv = serving.GenerativeServer(net, cfg, mesh=mesh)
    with srv:
        futs = [srv.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(120) for f in futs]
        stats = srv.stats()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)

    assert stats["num_replicas"] == 2
    # least-loaded routing spread the burst over both replicas
    per_rep = stats["replicas"]
    assert len(per_rep) == 2
    assert all(r["completed"] >= 1 for r in per_rep)
    assert sum(r["completed"] for r in per_rep) == 4
    # one decode compile per replica for the whole lifetime
    for rep in srv.replicas:
        sigs = rep.engine.compiled_signatures()
        assert sigs.count(("step",)) == 1
    # pool placement agrees with the planner on the tp submesh
    tp_mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    eng = srv.replicas[0].engine
    assert eng.kv_pool_bytes() == plan_kv_pool(
        net.config.num_layers, net.config.num_kv_heads,
        net.config.head_dim, num_blocks=eng.num_blocks,
        block_size=eng.block_size, mesh=tp_mesh)


# --- two kinds of per-request state in one ledger ----------------------------

@pytest.mark.parametrize("layers,state_shape", [
    (("kv", "kv"), None),                               # a Llama: K/V only
    (("state", "kv", "state", "state"), (3, 64)),       # conv states beside
    (("state", "state", "kv", "kv", "state"), (2, 8, 4)),
])
def test_paged_manager_prices_blocks_and_slot_state(layers, state_shape):
    """``CacheSpec`` says which layers own K/V blocks and which a fixed
    per-slot state; the manager reports bytes of both kinds, blocks over
    the K/V layers only."""
    from mxnet_tpu.serving import PagedKVCacheManager
    from mxnet_tpu.serving.kv_cache import CacheSpec

    spec = CacheSpec(layers, num_kv_heads=2, head_dim=16,
                     state_shape=state_shape)
    n_kv, n_state = layers.count("kv"), layers.count("state")
    assert (spec.kv_layers, spec.state_layers) == (n_kv, n_state)
    per_block = 2 * n_kv * 2 * 4 * 16 * 2
    per_slot = n_state * int(np.prod(state_shape or (0,))) * 2
    assert spec.kv_bytes_per_block(block_size=4, itemsize=2) == per_block
    assert spec.state_bytes_per_slot(itemsize=2) == per_slot
    mgr = PagedKVCacheManager(3, 32, num_blocks=16, block_size=4,
                              kv_bytes_per_block=per_block,
                              state_bytes_per_slot=per_slot)
    mgr.admit("a", 5, 3)          # 2 blocks
    mgr.admit("b", 9, 4)          # 3 blocks held, 4 at most
    st = mgr.stats()
    assert st["kv_block_bytes_in_use"] == 5 * per_block
    assert st["state_bytes_per_slot"] == per_slot
    assert st["state_bytes_in_use"] == 2 * per_slot
    mgr.evict(mgr.active_slots()[0])
    assert mgr.stats()["state_bytes_in_use"] == per_slot
    mgr.check()


def test_cache_spec_refuses_what_it_cannot_price():
    from mxnet_tpu.serving.kv_cache import CacheSpec

    with pytest.raises(mx.MXNetError, match="unknown cache kind"):
        CacheSpec(("kv", "window"), 2, 16)
    with pytest.raises(mx.MXNetError, match="latent layers need"):
        CacheSpec(("kv", "latent"), 2, 16)
    with pytest.raises(mx.MXNetError, match="state_shape"):
        CacheSpec(("kv", "state"), 2, 16)


# --- what an option needs of a cache: one table, one function ------------------

def _refusal_specs():
    from mxnet_tpu.models.decoder import BlockDecoding, CacheSpec

    blocks = BlockDecoding(block_len=4, mask_id=7, steps=2, threshold=0.9)
    kw = dict(num_kv_heads=2, head_dim=16)
    return {
        "plain": CacheSpec(("kv", "kv"), **kw),
        "loop": CacheSpec(("kv", "kv"), passes=2, **kw),
        "latent": CacheSpec(("latent", "kv"), latent_dim=8, index_dim=4,
                            select_topk=2, **kw),
        # K/V layers that keep an index key beside and select
        "kv_select": CacheSpec(("kv", "kv"), index_dim=4, select_topk=2,
                               **kw),
        "block": CacheSpec(("kv",), decoding=blocks, **kw),
        "state": CacheSpec(("state", "kv"), state_shape=(3, 8), **kw),
        # routed experts and no state layer: the same trait
        "experts": CacheSpec(("kv",), expert_layers=1, num_experts=4, **kw),
        # what a block decoder cannot have beside it
        "block+state": CacheSpec(("state", "kv"), state_shape=(3, 8),
                                 decoding=blocks, **kw),
    }


#: an option -> (how a caller asks for it, the keyword its sentence names)
_ASKED = {"spec": (dict(spec_k=2), "spec_k"),
          "mesh": (dict(mesh=object()), "mesh="),
          "int8": (dict(int8=True), "int8=True"),
          "radix": (dict(radix=True), "radix_cache=True")}
_EVERYTHING = {k: v for kw, _ in _ASKED.values() for k, v in kw.items()}


def _refusal_cases():
    from mxnet_tpu.serving.generative import REFUSALS

    cases = [(trait, trait, option) for trait, option in sorted(REFUSALS)
             if option in _ASKED]
    cases += [("experts", "state", option) for option in sorted(_ASKED)]
    # every row of the table is one of these cases, or the block decoder's
    assert {c[1:] for c in cases} | {("block", "state")} == set(REFUSALS)
    return cases


@pytest.mark.parametrize("name,trait,option", _refusal_cases())
def test_an_option_a_cache_kind_cannot_give_is_refused_by_the_tables_row(
        name, trait, option):
    from mxnet_tpu.serving.generative import REFUSALS, refuse

    kw, keyword = _ASKED[option]
    with pytest.raises(mx.MXNetError) as exc:
        refuse(_refusal_specs()[name], **kw)
    assert str(exc.value) == REFUSALS[trait, option]
    assert keyword in str(exc.value)


def test_a_block_decoder_beside_a_state_layer_is_refused_unasked():
    from mxnet_tpu.serving.generative import REFUSALS, refuse

    with pytest.raises(mx.MXNetError) as exc:
        refuse(_refusal_specs()["block+state"])
    assert str(exc.value) == REFUSALS["block", "state"]
    assert "block decoder" in str(exc.value)
    refuse(_refusal_specs()["block"])      # alone it is served


@pytest.mark.parametrize("option", sorted(_ASKED) + ["all"])
def test_a_plain_kv_spec_is_refused_nothing(option):
    from mxnet_tpu.serving.generative import refuse

    kw = _EVERYTHING if option == "all" else _ASKED[option][0]
    assert refuse(_refusal_specs()["plain"], **kw) is None


def test_the_first_option_asked_for_and_the_first_trait_are_the_ones_named():
    """In the order the engine has always checked them: spec, mesh, int8,
    then the replica's radix; a stack run several times before any other
    trait."""
    from mxnet_tpu.serving.generative import REFUSALS, refuse

    specs = _refusal_specs()
    for drop, first in ((), "spec"), (("spec_k",), "mesh"), \
            (("spec_k", "mesh"), "int8"), (("spec_k", "mesh", "int8"), "radix"):
        kw = {k: v for k, v in _EVERYTHING.items() if k not in drop}
        with pytest.raises(mx.MXNetError) as exc:
            refuse(specs["state"], **kw)
        assert str(exc.value) == REFUSALS["state", first]
    for name, phrase in (("loop", "several times"), ("latent", "latent"),
                         ("block", "block decoder"),
                         ("state", "per-slot state")):
        with pytest.raises(mx.MXNetError, match=phrase):
            refuse(specs[name], radix=True)

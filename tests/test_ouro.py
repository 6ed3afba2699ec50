"""Ouro (``models/ouro.py``: a stack of layers run several times a token) and
the loop over passes of the shared paged programs (``models/decoder.py``)
against the plain reference (``chipbench/references/ouro.py``) at a tiny size
on the CPU: the Gluon forward, prefill then decode through the paged cache at
every position and prefill bucket, slots admitted at different times beside a
vacant one, the lanes and the tick that runs ahead, the planted faults the
comparison refuses, what the spec, the planner, the engine and the lane log
count a pass, what the engine refuses by name, a pool that gates admission,
and a rehearsal of the benchmark's cell.

hidden 64, 3 layers run 3 times, 4 query and 4 KV heads of 16, vocabulary
256; float32 weights.
"""
import importlib.util
import json
import os
import re
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.models import ouro
from mxnet_tpu.models.decoder import CacheSpec, Causal, PagedDecoder
from mxnet_tpu.ops import paged_attention
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
DATA = os.path.join(BENCH, "tests", "data_ouro")
CELL_CONFIG = os.path.join(BENCH, "configs", "ouro_2_6b.json")
CELL_TRAFFIC = os.path.join(BENCH, "traffic", "reason_decode_sat_s8.json")

#: float32 on both sides; the served products run at the CPU's default
#: precision and sum in another order than the reference's at ``highest``:
#: 5e-4 of the largest logit.  The mildest planted fault (bfloat16 products)
#: moves a logit by ten times that, the others by a hundred times and more
TOLERANCE = 5e-4


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_ouro_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "ouro.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size,
            "total_ut_steps": cfg.total_ut_steps,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0, 0.3),
    so that attention is far from uniform), the norms' weights seeded too, so
    that a norm left out or run once shows -> (net, the reference's tree)."""
    net = ouro.ouro_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    top["norm"] = 1.0 + 0.3 * jax.random.normal(key, top["norm"].shape)
    layers = []
    for l, lr in enumerate(net.layers):
        w = ref.init_layer(ref.layer_key(key, l), cfg, jnp.float32)
        assert sorted(w) == lr._names
        for i, n in enumerate(ref.NORMS):
            w[n] = w[n] + 0.3 * jax.random.normal(
                jax.random.fold_in(key, 100 * l + i), w[n].shape)
        for n in lr._names:
            getattr(lr, n).set_data(nd.NDArray(w[n]))
        layers.append(w)
    net.embed_tokens.weight.set_data(nd.NDArray(top["emb"]))
    net.norm.weight.set_data(nd.NDArray(top["norm"]))
    net.lm_head.weight.set_data(nd.NDArray(top["head"]))
    return net, {"top": top, "layers": layers}


@pytest.fixture(scope="module")
def tiny(ref):
    net, weights = _net_and_weights(ref)
    return net, weights, _ref_cfg(net.config)


def _ref_logits(ref, tiny, ids, fault=None):
    _net, weights, cfg = tiny
    return np.asarray(ref.forward(cfg, weights, np.asarray(ids), fault))


def _server(net, **kw):
    cfg = dict(max_batch=2, max_length=64, min_length=8, num_slots=3,
               block_size=4)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


def _prefill_and_commit(eng, seq, t0, slot=0, pad=77):
    dec, w = eng._dec, eng._w
    lb = max(8, 1 << (t0 - 1).bit_length())
    ids = np.full((1, lb), pad, np.int32)    # what padding must not leak
    ids[0, :t0] = seq[:t0]
    rows, lg = dec._prefill_rows_impl(w, jnp.asarray(ids), jnp.asarray([t0]))
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    return rows, np.asarray(lg)[0]


def _step_logits(eng, ids_t, pos, tables=None):
    lg, eng._pool = eng._dec._step_blocks_impl(
        eng._w, eng._pool,
        jnp.asarray(eng._tables if tables is None else tables),
        jnp.asarray(ids_t, jnp.int32), jnp.asarray(pos, jnp.int32))
    return np.asarray(lg)


def _teacher_forced_logits(eng, seq, t0, slot=0):
    """Prefill ``seq[:t0]`` in a padded bucket, hand every pass's rows over,
    then decode the rest of ``seq`` token by token through the engine's own
    programs -> (len(seq) - t0 + 1, vocab)."""
    out = [_prefill_and_commit(eng, seq, t0, slot)[1]]
    for t in range(t0, len(seq)):
        ids_t = np.zeros(eng.num_slots, np.int32)
        pos = np.zeros(eng.num_slots, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        out.append(_step_logits(eng, ids_t, pos)[slot])
    return np.stack(out)


def _close(got, want):
    return np.abs(got - want).max() < TOLERANCE * np.abs(want).max()


# --- the mathematics -----------------------------------------------------------

def test_gluon_forward_equals_reference_logits(ref, tiny):
    net = tiny[0]
    ids = np.random.RandomState(0).randint(1, 256, size=(2, 19))
    got = net(nd.array(ids, dtype="int32")).asnumpy()
    for b in range(2):
        assert _close(got[b], _ref_logits(ref, tiny, ids[b]))


@pytest.mark.parametrize("t0", [1, 2, 5, 8, 13, 20, 33])
def test_prefill_then_decode_equals_reference_at_every_position(ref, tiny, t0):
    """Ragged prompts in the buckets of 8, 16, 32 and 64 positions, padded
    with an id whose rows must not leak, then nine decoded tokens: LOGITS at
    every served position (``TOLERANCE``)."""
    eng = _server(tiny[0]).engine
    seq = np.random.RandomState(100 + t0).randint(1, 256, size=t0 + 9)
    got = _teacher_forced_logits(eng, seq, t0)
    want = _ref_logits(ref, tiny, seq)[t0 - 1:]
    assert got.shape == want.shape
    assert _close(got, want)


def test_slots_admitted_at_different_times_beside_a_vacant_one(ref, tiny):
    """Slot 0 decodes four tokens alone, then slot 2 is admitted and both
    decode in one program, each at its own position; slot 1 stays vacant (its
    table row the sentinel: its write drops in every pass)."""
    eng = _server(tiny[0]).engine
    rs = np.random.RandomState(7)
    a, b = rs.randint(1, 256, size=21), rs.randint(1, 256, size=14)
    got_a = [_prefill_and_commit(eng, a, 6, slot=0)[1]]
    got_b = []
    for t in range(6, 21):
        if t == 10:
            got_b.append(_prefill_and_commit(eng, b, 3, slot=2)[1])
        ids_t, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        ids_t[0], pos[0] = a[t], t
        tb = 3 + (t - 10)
        if t >= 10 and tb < len(b):
            ids_t[2], pos[2] = b[tb], tb
        lg = _step_logits(eng, ids_t, pos)
        got_a.append(lg[0])
        if t >= 10 and tb < len(b):
            got_b.append(lg[2])
    assert _close(np.stack(got_a), _ref_logits(ref, tiny, a)[5:])
    assert _close(np.stack(got_b), _ref_logits(ref, tiny, b)[2:])
    # the vacant slot's blocks were never written, in any pass's part
    kp = np.asarray(eng._pool[0][0])
    mine = np.arange(eng.max_blocks, 2 * eng.max_blocks)
    for t in range(3):
        assert not kp[mine + t * eng.num_blocks].any()


def test_a_batch_of_two_prompts_prefills_as_each_alone(ref, tiny):
    """What works for nothing: a prefill batch of two rows of different true
    lengths goes through the loop as one row does."""
    eng = _server(tiny[0]).engine
    rs = np.random.RandomState(3)
    seqs = [rs.randint(1, 256, size=n) for n in (11, 5)]
    ids = np.full((2, 16), 77, np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    rows, lg = eng._dec._prefill_rows_impl(
        eng._w, jnp.asarray(ids), jnp.asarray([11, 5]))
    assert rows[0][0].shape == (3, 2, 4, 16, 16)     # (passes, B, Hkv, Lp, hd)
    for i, s in enumerate(seqs):
        assert _close(np.asarray(lg)[i], _ref_logits(ref, tiny, s)[-1])


def test_served_tokens_follow_the_reference_through_the_tick_that_runs_ahead(
        ref, tiny):
    """Through the lanes: every generated token is the reference's argmax
    given what came before, prompts of ragged lengths, more requests than
    slots; and the ticks ran ahead of their bookkeeping (PR 39)."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 256, size=n) for n in (1, 3, 9, 17, 6)]
    since = time.perf_counter()
    with _server(tiny[0], num_slots=2) as srv:
        futs = [srv.submit(p, max_new_tokens=12) for p in prompts]
        outs = [f.result(180) for f in futs]
        assert srv.stats()["decode_steps_ahead"] > 0
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 12
        lg = _ref_logits(ref, tiny, o)
        for j in range(12):
            row = lg[len(p) - 1 + j]
            assert row[o[len(p) + j]] >= row.max() - 1e-3 * np.abs(row).max()
    ticks = tracing.lane_log("decode.tick", since=since)
    assert any(t["ahead"] for t in ticks)


@pytest.fixture(scope="module")
def served(tiny):
    """22 tokens, 9 of them the prompt -> (the tokens, the served logits)."""
    seq = np.random.RandomState(31).randint(1, 256, size=22)
    return seq, _teacher_forced_logits(_server(tiny[0]).engine, seq, 9)


@pytest.mark.parametrize("fault", ["passes_short", "norm_once", "shared_cache",
                                   "no_post_norms", "bfloat16"])
def test_a_planted_fault_is_refused(ref, tiny, served, fault):
    """One pass fewer; the final norm once instead of a pass; pass t reading
    pass t-1's rows; the post-sublayer norms left out; bfloat16 products where
    the configuration says float32: the served logits are not the faulty
    forward's by ten tolerances at least, and they are the sound one's."""
    seq, got = served
    assert _close(got, _ref_logits(ref, tiny, seq)[8:])
    wrong = _ref_logits(ref, tiny, seq, fault)[8:]
    assert np.abs(got - wrong).max() > 10 * TOLERANCE * np.abs(wrong).max()


def test_a_served_cache_shared_between_passes_is_refused(ref, tiny, monkeypatch):
    """Planted in the program: every pass addresses pass 0's blocks.  The
    prefill's logits stand (a pass's rows are its own until they are stored);
    the decoded positions' do not."""
    monkeypatch.setattr(
        paged_attention, "pass_blocks",
        lambda ids, t, nb, passes: jnp.where(ids < nb, ids, passes * nb))
    eng = _server(tiny[0]).engine
    seq = np.random.RandomState(32).randint(1, 256, size=18)
    got = _teacher_forced_logits(eng, seq, 9)
    want = _ref_logits(ref, tiny, seq)[8:]
    assert _close(got[0], want[0])
    assert np.abs(got[1:] - want[1:]).max() > 10 * TOLERANCE * np.abs(want).max()


# --- what the spec, the planner and the engine count a pass -------------------------

def test_the_published_spec_keeps_a_mebibyte_and_a_half_a_token():
    conf = ouro.OuroConfig()
    spec = ouro.OuroDecoder(ouro.OuroForCausalLM(conf), 1536).cache_spec()
    assert (spec.passes, spec.kv_layers, spec.num_kv_heads, spec.head_dim) \
        == (4, 48, 16, 128)
    assert spec.kv_bytes_per_block(1, 2) == 1572864 == 3 * 2 ** 19
    assert spec.kv_bytes_per_block(16, 2) == 24 * 2 ** 20
    fb = _bench_module("flops_bytes", "ouro_decode_step.py")
    assert fb.kv_bytes_per_token(json.load(open(CELL_CONFIG))) == 1572864


def test_pool_bytes_equal_the_planners_and_the_stats():
    from mxnet_tpu.memory import plan_kv_pool

    net = ouro.ouro_tiny()
    net.cast("bfloat16")
    net.initialize()
    srv = _server(net, num_slots=3, num_blocks=20)
    eng, spec = srv.engine, srv.engine.cache_spec
    assert (spec.passes, spec.layers) == (3, ("kv",) * 3)
    assert len(eng._pool) == 3 and eng._pool[0][0].shape == (3 * 20, 4, 4, 16)
    blocks = 2 * 3 * 3 * 20 * 4 * 4 * 16 * 2
    assert eng.kv_pool_bytes(by_kind=True) == {"kv_blocks": blocks,
                                               "slot_state": 0}
    assert eng.kv_pool_bytes() == blocks == plan_kv_pool(
        3, 4, 16, num_blocks=20, block_size=4, dtype="bfloat16", passes=3)
    assert plan_kv_pool(3, 4, 16, num_blocks=20, block_size=4,
                        dtype="bfloat16") == blocks // 3     # a stack run once
    per_token = 2 * 3 * 3 * 4 * 16 * 2
    assert eng.kv_bytes_per_token == per_token
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    assert st["cache_bytes"]["kv_blocks"] == blocks
    assert (st["cache_passes"], st["kv_bytes_per_token"], st["pool_tokens"]) \
        == (3, per_token, 80)
    assert st["pool_reserved_tokens"] == 0          # the request has left
    assert st["kv_cache"]["kv_block_bytes_in_use"] == 0
    assert srv.replicas[0].mgr.kv_bytes_per_block == 4 * per_token


def test_decode_step_bytes_total_to_the_issues_table():
    fb = _bench_module("flops_bytes", "ouro_decode_step.py")
    cfg = json.load(open(CELL_CONFIG))
    assert fb.layer_params(cfg) == 4 * 2048 * 2048 + 3 * 2048 * 5632 \
        + 4 * 2048 == 51388416
    assert fb.stack_params(cfg) == 2466643968
    assert fb.table_params(cfg) == 2667970560
    assert round(2 * fb.table_params(cfg) / 1e9, 2) == 5.34
    # a tick: the stack four times, the head and the final norm once
    assert fb.weight_bytes(cfg) == 2 * (4 * 2466643968 + 49152 * 2048 + 2048)
    assert fb.bytes_needed(cfg, 6, 3000) == fb.weight_bytes(cfg) \
        + 6 * 2048 * 2 + 3006 * 1572864
    assert fb.flops_needed(cfg, 1, 0) == 2 * (
        4 * 48 * (51388416 - 4 * 2048) + 49152 * 2048)
    # and the net holds the table's parameters, the final norm's and the gate's
    net = ouro.ouro_tiny()
    net.initialize()
    held = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    small = _ref_cfg(net.config)
    assert held == fb.table_params(small) + fb.other_params(small)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(CELL_CONFIG))
    assert cfg["reduced"] == {}
    published = {"hidden_size": 2048, "intermediate_size": 5632, "head_dim": 128,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "num_hidden_layers": 48, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "vocab_size": 49152,
                 "rope_theta": 1000000, "rms_norm_eps": 1e-06,
                 "max_position_embeddings": 65536, "model_type": "ouro"}
    assert {k: cfg[k] for k in published} == published
    conf = ouro.OuroConfig()
    assert (conf.hidden_size, conf.intermediate_size, conf.num_layers,
            conf.num_heads, conf.num_kv_heads, conf.head_dim, conf.vocab_size,
            conf.total_ut_steps) == (2048, 5632, 48, 16, 16, 128, 49152, 4)
    mix = json.load(open(CELL_TRAFFIC))
    assert (mix["clients"], mix["system"]["num_slots"]) == (16, 8)
    assert 288 <= mix["system"]["num_blocks"] <= 352
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = [w for w in bench["workloads"]
            if w["name"] == "ouro_2_6b.reason_decode_sat"]
    assert cell == [dict(cell[0], config="ouro_2_6b", chips=1,
                         traffic="reason_decode_sat_s8")]


# --- what is refused, loudly ---------------------------------------------------

@pytest.mark.parametrize("name,kw,says", [
    ("radix", dict(radix_cache=True), "radix_cache=True"),
    ("int8", dict(int8=True), "int8=True"),
])
def test_options_a_looped_decoder_has_not_are_refused_by_name(tiny, name, kw,
                                                              says):
    with pytest.raises(mx.MXNetError) as exc:
        serving.GenerativeServer(
            tiny[0], ServerConfig(max_batch=2, max_length=64, min_length=8,
                                  num_slots=2, **kw))
    assert says in str(exc.value) and "several times" in str(exc.value)


def test_speculation_and_a_mesh_are_refused_by_name(tiny):
    from mxnet_tpu.serving.generative import LlamaServingEngine

    for kw, says in ((dict(spec_k=2), "speculative"),
                     (dict(mesh=object()), "mesh-placed")):
        with pytest.raises(mx.MXNetError) as exc:
            LlamaServingEngine(tiny[0], max_len=64, num_slots=2,
                               block_size=4, **kw)
        assert says in str(exc.value) and "pass" in str(exc.value)
    with pytest.raises(mx.MXNetError) as exc:
        eng = _server(tiny[0]).engine
        eng._dec._prefill_suffix_impl(eng._w, [], jnp.zeros((1, 8), jnp.int32),
                                      jnp.asarray([3]), jnp.asarray([4]))
    assert "suffix prefill has no loop over passes" in str(exc.value)


def test_an_exit_threshold_under_one_is_refused_by_name():
    with pytest.raises(mx.MXNetError) as exc:
        ouro.ouro_tiny(early_exit_threshold=0.9)
    assert "early_exit_threshold 0.9 < 1" in str(exc.value)
    assert ouro.ouro_tiny().early_exit_gate.weight.shape == (1, 64)


def test_a_looped_spec_keeps_keys_and_values_only():
    for kw in (dict(layers=("kv", "state"), state_shape=(3, 8)),
               dict(layers=("kv",), expert_layers=1, num_experts=4)):
        with pytest.raises(mx.MXNetError) as exc:
            CacheSpec(num_kv_heads=2, head_dim=16, passes=2, **kw)
        assert "passes > 1" in str(exc.value)
    with pytest.raises(mx.MXNetError):
        CacheSpec(("kv",), 2, 16, passes=0)
    assert CacheSpec(("kv",), 2, 16).passes == 1
    assert CacheSpec(("kv",) * 2, 2, 16, passes=3).kv_bytes_per_block(4, 2) \
        == 3 * CacheSpec(("kv",) * 2, 2, 16).kv_bytes_per_block(4, 2)


def test_views_or_pools_that_do_not_match_the_spec_fail_loudly(tiny):
    """Today's repair: a ``zip`` no longer truncates in silence."""
    eng = _server(tiny[0]).engine
    dec, w = eng._dec, eng._w
    x = jnp.zeros((1, 8, 64))
    rope = (dec._cos[:8][None, None], dec._sin[:8][None, None])
    with pytest.raises(mx.MXNetError) as exc:
        PagedDecoder._layers(dec, w, x, rope, [Causal(8)] * 2)
    assert "2 cache views for 3 layers" in str(exc.value)
    with pytest.raises(mx.MXNetError) as exc:
        dec._step_blocks_impl(w, eng._pool[:2], jnp.asarray(eng._tables),
                              jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32))
    assert "a cache of 2 entries for the 3 layers" in str(exc.value)

    class Short(ouro.OuroDecoder):
        def cache_spec(self):
            return CacheSpec(("kv",) * 2, 4, 16, passes=3)

    net = tiny[0]
    net.serving_decoder = lambda max_len: Short(net, max_len)
    try:
        with pytest.raises(mx.MXNetError) as exc:
            _server(net)
    finally:
        del net.serving_decoder
    assert "states 2 layers and its weights hold 3" in str(exc.value)


# --- one traced stack, one loop ---------------------------------------------------

def test_the_programs_hold_the_stack_once_whatever_the_passes(tiny):
    """The loop over passes is a loop on the device: the step and prefill
    programs of 2 and of 3 passes hold as many matrix products, under a
    ``loop_pass`` scope; and their names are the ones the benchmark reads."""
    def lowered(net):
        eng = _server(net).engine
        ids, t0s = np.ones((1, 8), np.int32), np.full(1, 6, np.int32)
        out = {"prefill": eng._prefill.lower(eng._w, eng._dev(ids),
                                             eng._dev(t0s)),
               "step": eng._step.lower(
                   eng._w, eng._pool, eng._dev(eng._tables),
                   eng._dev(eng._last), eng._toks, eng._dev(eng._pos))}
        _first, rows = eng.prefill_rows(ids, t0s)
        out["scatter"] = eng._scatter.lower(
            eng._pool, rows, eng._dev(np.full(2, eng.num_blocks, np.int32)))
        return out

    three = lowered(tiny[0])
    net2 = ouro.ouro_tiny(total_ut_steps=2)
    net2.initialize()
    two = lowered(net2)
    programs = _bench_module("families", "ouro.py").Cell.programs
    for key in ("step", "prefill"):
        text = three[key].as_text(debug_info=True)
        dots = text.count("stablehlo.dot_general")
        assert dots == two[key].as_text().count("stablehlo.dot_general") > 0
        assert text.count("stablehlo.while") >= 1 and "loop_pass" in text
    for key, low in three.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)


# --- the lane log, the stats, the counter -------------------------------------------

def test_lane_log_counts_the_passes(tiny, monkeypatch):
    from mxnet_tpu import telemetry

    seen = []
    monkeypatch.setattr(telemetry, "count",
                        lambda name, n=1: seen.append((name, n)))
    since = time.perf_counter()
    with _server(tiny[0], num_slots=2, max_batch=1) as srv:
        futs = [srv.submit(np.arange(1, 1 + n), max_new_tokens=4)
                for n in (3, 9, 12)]
        for f in futs:
            f.result(120)
        st = srv.stats()
    per_token = 2 * 3 * 3 * 4 * 16 * 4
    assert st["kv_bytes_per_token"] == per_token and st["cache_passes"] == 3
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert ticks and len(batches) == 3
    for rec in ticks:
        assert rec["passes"] == 3
        assert rec["kv_bytes"] == rec["kv_tokens"] * per_token > 0
        # as the record is written: a tick's finished requests have given
        # their blocks back
        assert 0 <= rec["pool_reserved_tokens"] <= st["pool_tokens"]
        assert rec["pool_reserved_tokens"] % 4 == 0
    assert max(rec["pool_reserved_tokens"] for rec in ticks) > 0
    for rec, n in zip(batches, (3, 9, 12)):
        assert rec["passes"] == 3 and rec["kv_bytes"] == n * per_token
    for first in (ticks[0], batches[0]):
        assert (first["cache_passes"], first["kv_bytes_per_token"],
                first["pool_tokens"]) == (3, per_token, st["pool_tokens"])
    assert "cache_passes" not in ticks[-1] and "pool_tokens" not in ticks[-1]
    applied = sum(n for name, n in seen
                  if name == "serving.decode.layer_applications")
    assert applied == len(ticks) * 3 * 3


def test_a_stack_run_once_says_one_pass():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    since = time.perf_counter()
    with serving.GenerativeServer(net, ServerConfig(
            max_batch=2, max_length=64, min_length=8, num_slots=2)) as srv:
        srv.generate(np.arange(1, 7), max_new_tokens=3)
        st = srv.stats()
    per_token = 2 * 2 * 2 * 16 * 4
    assert (st["cache_passes"], st["kv_bytes_per_token"]) == (1, per_token)
    tick = tracing.lane_log("decode.tick", since=since)[0]
    batch = tracing.lane_log("prefill.batch", since=since)[0]
    assert tick["passes"] == 1 == batch["passes"]
    assert tick["kv_bytes"] == tick["kv_tokens"] * per_token
    assert batch["kv_bytes"] == 6 * per_token


def test_a_pool_too_small_gates_a_request_and_admits_it_when_blocks_free(tiny):
    """Eight blocks of four tokens under three slots: a request of 9 + 14
    tokens holds three and claims six, and with a second one of the kind
    neither could reach its six, so the second waits for BLOCKS with two
    slots free (``prefill.gated`` reason ``block``) and is admitted when the
    first leaves."""
    rs = np.random.RandomState(8)
    prompts = [rs.randint(1, 256, size=9) for _ in range(2)]
    since = time.perf_counter()
    with _server(tiny[0], num_slots=3, max_batch=1, num_blocks=8) as srv:
        futs = [srv.submit(p, max_new_tokens=14) for p in prompts]
        outs = [f.result(180) for f in futs]
        lane = srv.stats()["lanes"][0]
    assert all(len(o) == 23 for o in outs)
    gated = tracing.lane_log("prefill.gated", since=since)
    assert gated and {g["reason"] for g in gated} == {"block"}
    assert lane["gates"].get("block", 0) >= 1 and "slot" not in lane["gates"]
    batches = tracing.lane_log("prefill.batch", since=since)
    assert len(batches) == 2 and batches[1]["free_slots"] >= 2
    ticks = tracing.lane_log("decode.tick", since=since)
    assert max(t["n_active"] for t in ticks) == 1
    assert max(t["pool_reserved_tokens"] for t in ticks) == 24
    # a lane gated on blocks is not about to use the device: the first
    # request's ticks ran ahead of their bookkeeping although a request
    # waited and two slots stood free
    alone = [t for t in ticks if t["request_ids"] == ticks[0]["request_ids"]]
    assert sum(t["ahead"] for t in alone[2:]) >= len(alone[2:]) - 2


# --- the benchmark's readers on a program without the fields ------------------------

def test_the_new_readers_find_nothing_on_records_without_their_fields(
        monkeypatch):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    cfg = json.load(open(CELL_CONFIG))
    old = [{"replica": 0, "seq": 1, "n_active": 2, "kv_tokens": 40,
            "t_tok": 1.5}]
    monkeypatch.setattr(tracing, "lane_log", lambda *a, **k: old)
    obs = {"config": cfg, "t0_abs": 1.0, "window_s": 1.0, "trace": None,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "programs": {"step": "^jit__step_fn"}, "trace_host_window": None}
    for name in ("loop_step_roofline", "kv_bytes_share", "pool_reserved_share"):
        assert _bench_module("layer_metrics", name + ".py").read(obs) is None
    # and on another family's configuration, whatever its records say
    obs["config"] = {"hidden_size": 64}
    new = [dict(old[0], passes=3, kv_bytes=9, pool_reserved_tokens=8)]
    monkeypatch.setattr(tracing, "lane_log", lambda *a, **k: new)
    for name in ("loop_step_roofline", "kv_bytes_share"):
        assert _bench_module("layer_metrics", name + ".py").read(obs) is None


def test_the_share_readers_read_the_records_fields(monkeypatch):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    cfg = json.load(open(CELL_CONFIG))
    fb = _bench_module("flops_bytes", "ouro_decode_step.py")
    recs = [{"replica": 0, "seq": s, "n_active": 6, "kv_tokens": 3000,
             "passes": 4, "kv_bytes": 3000 * 1572864, "t_tok": 1.5,
             "pool_reserved_tokens": r} for s, r in ((2, 4096), (3, 5120))]
    recs[0]["pool_tokens"] = 5120
    monkeypatch.setattr(tracing, "lane_log", lambda *a, **k: recs)
    obs = {"config": cfg, "t0_abs": 1.0, "window_s": 1.0}
    share = _bench_module("layer_metrics", "kv_bytes_share.py").read(obs)
    assert share == pytest.approx(
        100 * 3000 * 1572864 / fb.bytes_needed(cfg, 6, 3000))
    assert _bench_module("layer_metrics",
                         "pool_reserved_share.py").read(obs) == 90.0


# --- the cell, rehearsed, and what its check refuses ------------------------------

@pytest.fixture
def harness(monkeypatch, tmp_path):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as harness

    # a trace directory of its own: the checkout's one ``.chipbench_trace``
    # is shared by every test process, and a traced rehearsal that starts in
    # another worker removes it under this one (PERF.md section 7(h))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    return harness


def _compared(out):
    compared = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            compared[name] = float(rest.split(" limit ")[0])
    return compared


LIMIT_ROWS = [("served_logit_gap_max", "gap_limit"),
              ("served_logit_gap_mean", "gap_mean_limit")]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_ouro``: the new
    family, reference and readers at a tiny size, 20 blocks of 4 tokens under 4
    slots, so that the pool gates.  What is held here does not depend on how
    many ticks the machine's load lets into the window."""
    res = harness.run(["--workload", "tiny_ouro.closed", "--seed",
                       "4000000007", "--seconds", "2", "--trace", str(trace),
                       "--control", "1"], require_tpu=False, data_dir=DATA)
    compared = _compared(capsys.readouterr().out)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    chk = json.load(open(os.path.join(DATA, "traffic", "closed.json")))["check"]
    for prefix in ("control.", "control_shared."):
        for row, key in LIMIT_ROWS:
            assert compared[row] <= chk[key] < compared[prefix + row], row
        assert compared[prefix + "passes_every_limit"] == 0
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report where the window held a tick
        assert not {"loop_step_roofline", "decode_step_ms"} \
            & set(res["metrics"])
        share = res["metrics"].get("kv_bytes_share")
        assert share is None or 0 < share["value"] < 100
        held = res["metrics"].get("pool_reserved_share")
        assert held is None or 0 < held["value"] <= 100
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def test_rehearsal_reports_the_parked_share(harness, tmp_path):
    """The rehearsal's cell with ``parked_slot_share`` listed for it, as the
    repo's ``BENCHMARK.json`` lists it for ``ouro_2_6b.reason_decode_sat``: 20
    blocks of 4 under 4 slots whose requests claim up to 12, so slots park;
    the traced run's line carries the share, every request finishes and the
    check holds as it does with nothing parked."""
    import shutil

    data = str(tmp_path / "data")
    shutil.copytree(DATA, data)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[
            "parked_slot_share"]
    assert entry["workloads"] == ["ouro_2_6b.reason_decode_sat"]
    path = os.path.join(data, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(entry, workloads=["tiny_ouro.closed"]))
    with open(path, "w") as f:
        json.dump(bench, f)
    since = time.perf_counter()
    res = harness.run(["--workload", "tiny_ouro.closed", "--seed", "2147483659",
                       "--seconds", "2", "--trace", "1"],
                      require_tpu=False, data_dir=data)
    assert res["correct"] is True and res["failed"] == 0
    ticks = tracing.lane_log("decode.tick", since=since)
    assert ticks and all("n_parked" in t for t in ticks)
    share = res["metrics"].get("parked_slot_share")
    if share is not None:           # the window held a tick
        assert share["unit"] == "%" and 0 <= share["value"] < 100


def test_the_final_norm_once_is_not_correct(harness, capsys, monkeypatch):
    """Planted in the program: the final norm after the last pass only."""
    monkeypatch.setattr(ouro.OuroDecoder, "end_pass", lambda self, w, x: x)
    monkeypatch.setattr(
        ouro.OuroDecoder, "_logits",
        lambda self, w, x: ouro.rms_norm(x, w["norm"], self.cfg.norm_eps)
        @ w["head"].T)
    res = harness.run(["--workload", "tiny_ouro.closed", "--seed", "11",
                       "--seconds", "2", "--trace", "0", "--control", "0"],
                      require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "FAILED" in out

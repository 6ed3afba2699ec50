"""LFM2-MoE (``models/lfm2.py``) against its plain reference
(``chipbench/references/lfm2.py``) at a tiny size on the CPU: the Gluon
forward, prefill then decode through ``GenerativeServer`` at every
position, slot reuse, the conv recurrence, what the engine refuses for a
model with per-slot state, the bytes a decode tick needs, the new
lane-log fields, and a rehearsal of the benchmark's cell.

hidden 64, one dense layer then two periods (attention, conv, conv,
conv), 8 experts top 2, vocabulary 256; float32 weights.
"""
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, serving
from mxnet_tpu.models import lfm2
from mxnet_tpu.models.decoder import rms_norm
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_lfm2_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "lfm2.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "head_dim": cfg.head_dim,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "vocab_size": cfg.vocab_size,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "norm_eps": cfg.norm_eps, "num_hidden_layers": cfg.num_layers,
            "num_dense_layers": cfg.num_dense_layers,
            "layer_types": cfg.layer_types,
            "conv_L_cache": cfg.conv_L_cache,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "use_expert_bias": cfg.use_expert_bias,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing and attention are far from uniform) -> (net,
    the reference's weight tree)."""
    net = lfm2.lfm2_moe_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    layers = []
    for l, lr in enumerate(net.layers):
        w = ref.init_layer(ref.layer_key(key, l), cfg, jnp.float32,
                           ref.layer_kind(cfg, l))
        assert sorted(w) == lr._names
        for n in lr._names:
            getattr(lr, n).set_data(nd.NDArray(w[n]))
        layers.append(w)
    net.embed_tokens.weight.set_data(nd.NDArray(top["emb"]))
    net.norm.weight.set_data(nd.NDArray(top["norm"]))
    return net, {"top": top, "layers": layers}


@pytest.fixture(scope="module")
def tiny(ref):
    net, weights = _net_and_weights(ref)
    return net, weights, _ref_cfg(net.config)


def _ref_logits(ref, tiny, ids):
    _net, weights, cfg = tiny
    return np.asarray(ref.forward(cfg, weights, np.asarray(ids)))


# --- the mathematics, once ----------------------------------------------------

def test_gluon_forward_equals_reference_logits(ref, tiny):
    net = tiny[0]
    ids = np.random.RandomState(0).randint(0, 256, size=(2, 21))
    got = net(nd.array(ids, dtype="int32")).asnumpy()
    for b in range(2):
        want = _ref_logits(ref, tiny, ids[b])
        assert np.abs(got[b] - want).max() < 2e-4 * np.abs(want).max()


def test_conv_step_recurrence_equals_full_causal_conv(tiny):
    """The state ring, one position at a time, against the whole-sequence
    convolution, starting from a prompt shorter than the taps."""
    net = tiny[0]
    math = lfm2.Lfm2Math(net.config)
    p = {n: getattr(net.layers[0], n).data()._data
         for n in net.layers[0]._names}
    rs = np.random.RandomState(1)
    u = jnp.asarray(rs.randn(1, 11, 64), jnp.float32)
    full, z = math.short_conv(p, u, None)
    state = jnp.zeros((1, 3, 64), jnp.float32)
    for t in range(11):
        view = lfm2.StepView(state, jnp.asarray([t]))
        y, state = math.short_conv(p, u[:, t], view)
        assert np.allclose(y, full[:, t], atol=1e-5), t
    # the ring holds z of the last three positions, row t % 3
    for t in (8, 9, 10):
        assert np.allclose(state[0, t % 3], z[0, t])


@pytest.mark.parametrize("t0", [1, 2, 3, 7, 16])
def test_prefill_hands_on_the_state_of_the_true_length(tiny, t0):
    """Prompts padded to a bucket of 16: the state is z at t0-3 .. t0-1,
    zeros where the prompt is shorter, never the padded end's."""
    net = tiny[0]
    dec = lfm2.Lfm2Decoder(net, 32)
    w = dec._weights()
    ids = np.zeros((1, 16), np.int32)
    ids[0, :t0] = np.random.RandomState(t0).randint(1, 256, t0)
    ids[0, t0:] = 77                      # what padding must not leak
    rows, _lg, _c = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                           jnp.asarray([t0]))
    math = lfm2.Lfm2Math(net.config)
    p = w["layers"][0]
    x = w["emb"][jnp.asarray(ids)]
    _y, z = math.short_conv(p, rms_norm(x, p["op_norm"], 1e-5), None)
    state = np.asarray(rows[0])[0]
    for r in range(3):
        src = [p_ for p_ in range(t0 - 3, t0) if p_ % 3 == r][0]
        want = np.zeros(64) if src < 0 else np.asarray(z[0, src])
        assert np.allclose(state[r], want, atol=1e-6), (r, src)


# --- through GenerativeServer -------------------------------------------------

def _server(net, **kw):
    cfg = dict(max_batch=2, max_length=64, min_length=8, num_slots=3,
               block_size=4)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


def _teacher_forced_logits(eng, seq, t0, slot=0):
    """Prefill ``seq[:t0]`` then decode the rest of ``seq`` token by token
    through the engine's own programs, reading the LOGITS of every
    position from the prefill's last row on (the served programs return
    tokens; the decoder underneath gives the logits they are the argmax
    of), with the decode attention the engine chose.
    -> (len(seq) - t0 + 1, vocab)."""
    dec, w = eng._dec, eng._w
    lb = max(8, 1 << (t0 - 1).bit_length())
    ids = np.zeros((1, lb), np.int32)
    ids[0, :t0] = seq[:t0]
    rows, lg, _c = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                          jnp.asarray([t0]))
    out = [np.asarray(lg)[0]]
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    for t in range(t0, len(seq)):
        ids_t = np.zeros(eng.num_slots, np.int32)
        pos = np.zeros(eng.num_slots, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        lg, eng._pool, _c = dec._step_blocks_impl(
            w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(ids_t),
            jnp.asarray(pos),
            paged_kernel=eng.decode_attention == "paged_kernel")
        out.append(np.asarray(lg)[slot])
    return np.stack(out)


@pytest.mark.parametrize("t0", [1, 2, 5, 13, 20])
def test_prefill_then_decode_equals_reference_at_every_position(ref, tiny, t0):
    """Ragged prompts shorter and longer than a bucket, and shorter than the
    conv's three taps: prefill through the cache hand-over, then every
    decode step, against the reference's full forward."""
    net = tiny[0]
    eng = _server(net).engine
    seq = np.random.RandomState(100 + t0).randint(1, 256, size=t0 + 9)
    got = _teacher_forced_logits(eng, seq, t0)
    want = _ref_logits(ref, tiny, seq)[t0 - 1:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 5e-4 * np.abs(want).max()


def test_served_tokens_follow_the_reference(ref, tiny):
    """Through the lanes: every generated token is the reference's argmax
    given what came before, for prompts of ragged lengths in one batch."""
    net = tiny[0]
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 256, size=n) for n in (1, 3, 9, 17)]
    with _server(net) as srv:
        futs = [srv.submit(p, max_new_tokens=5) for p in prompts]
        outs = [f.result(120) for f in futs]
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 5
        lg = _ref_logits(ref, tiny, o)
        for j in range(5):
            row = lg[len(p) - 1 + j]
            # the served token is the reference's best, up to a tie
            assert row[o[len(p) + j]] >= row.max() - 1e-3 * np.abs(row).max()


def test_freed_slot_readmitted_gives_a_fresh_servers_logits(tiny):
    """One slot: the second request reuses the first's slot and blocks;
    its logits are those of a server that never saw the first."""
    net = tiny[0]
    rs = np.random.RandomState(9)
    first = rs.randint(1, 256, size=19)
    second = rs.randint(1, 256, size=12)
    used = _server(net, num_slots=1).engine
    _teacher_forced_logits(used, first, 11)
    used.clear_slot(0)
    got = _teacher_forced_logits(used, second, 2)
    fresh = _teacher_forced_logits(_server(net, num_slots=1).engine,
                                   second, 2)
    assert np.array_equal(got, fresh)


def test_step_rerun_at_one_position_leaves_the_state_alone(tiny):
    """A slot committed but not yet adopted is stepped by its neighbours'
    ticks at its own position: the ring write is idempotent, as the K/V
    row write is."""
    net = tiny[0]
    eng = _server(net, num_slots=1).engine
    seq = np.random.RandomState(2).randint(1, 256, size=9)
    dec, w = eng._dec, eng._w
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = seq[:5]
    rows, _lg, _c = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                           jnp.asarray([5]))
    eng.commit_rows(rows, np.asarray([0]), [list(range(eng.max_blocks))],
                    np.asarray([5]), np.asarray([seq[4]]))
    args = (jnp.asarray(eng._tables), jnp.asarray(seq[5:6]),
            jnp.asarray([5]))
    lg1, pool1, _ = dec._step_blocks_impl(w, eng._pool, *args)
    lg2, pool2, _ = dec._step_blocks_impl(w, pool1, *args)
    assert np.array_equal(np.asarray(lg1), np.asarray(lg2))
    for a, b in zip(jax.tree_util.tree_leaves(pool1),
                    jax.tree_util.tree_leaves(pool2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --- heads of 64: two KV heads to a lane row, read by the paged kernel -----------

def _as_on_a_chip(patch):
    """Steer an engine built here as a TPU would: ``applicable`` sees
    the platform ``tpu``, and the kernel runs through the interpreter."""
    import functools

    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops import paged_attention as pa

    real = pa.applicable
    patch.setattr(pa, "applicable",
                  lambda platform, *rest: real("tpu", *rest))
    patch.setattr(pa, "paged_decode_attention", functools.partial(
        pa._paged_decode_attention, interpret=pltpu.InterpretParams()))


@pytest.fixture(scope="module")
def tiny_hd64(ref):
    """The tiny net with heads of 64: hidden 256, 4 query / 2 KV heads."""
    return _net_and_weights(ref, seed=4, hidden_size=256)[0]


def test_engine_at_heads_of_64_here_stores_one_head_a_row(tiny_hd64):
    eng = _server(tiny_hd64, block_size=8).engine
    assert tiny_hd64.config.head_dim == 64
    assert (eng.decode_attention, eng.kv_pack) == ("gather", 1)
    shapes = {e[0].shape for e in eng._pool if isinstance(e, tuple)}
    assert shapes == {(eng.num_blocks, 2, 8, 64)}


@pytest.mark.parametrize("t0", [1, 7, 20])
def test_kernel_on_a_packed_pool_equals_gather_on_the_unpacked(
        tiny_hd64, monkeypatch, t0):
    """Prefill, commit and every decode step on an engine built as a chip
    builds it (``paged_kernel``, the pool ``(blocks, 1, bs, 128)``, the
    kernel in the interpreter) against the engine this machine builds
    (``gather``, ``(blocks, 2, bs, 64)``): the logits of every position,
    and the pools, read back through ``unpack_rows``."""
    from mxnet_tpu.ops.paged_attention import unpack_rows

    seq = np.random.RandomState(40 + t0).randint(1, 256, size=t0 + 6)
    plain = _server(tiny_hd64, block_size=8).engine
    assert plain.decode_attention == "gather"
    want = _teacher_forced_logits(plain, seq, t0)
    with monkeypatch.context() as patch:
        _as_on_a_chip(patch)
        packed = _server(tiny_hd64, block_size=8).engine
        got = _teacher_forced_logits(packed, seq, t0)
    assert (packed.decode_attention, packed.kv_pack) == ("paged_kernel", 2)
    shapes = {e[0].shape for e in packed._pool if isinstance(e, tuple)}
    assert shapes == {(packed.num_blocks, 1, 8, 128)}
    assert packed.kv_pool_bytes(by_kind=True) == \
        plain.kv_pool_bytes(by_kind=True)
    assert np.abs(got - want).max() < 5e-4 * np.abs(want).max()
    for a, b in zip(packed._pool, plain._pool):
        if not isinstance(a, tuple):
            assert np.allclose(a, b, atol=1e-4)
            continue
        for stored, rows in zip(a, b):
            assert np.allclose(unpack_rows(stored, 2), rows, atol=1e-4)
            assert np.asarray(rows).any()


# --- what is refused, loudly ---------------------------------------------------

def _draft():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


@pytest.mark.parametrize("name,kw,says", [
    ("radix", dict(radix_cache=True), "snapshot"),
    ("speculation", dict(draft_net="draft", spec_k=2), "rolled back"),
    ("int8", dict(int8=True), "int8=True"),
    ("mesh", dict(), "mesh"),
])
def test_options_refused_for_a_model_with_per_slot_state(tiny, name, kw, says):
    net = tiny[0]
    mesh = None
    if kw.get("draft_net") == "draft":
        kw = dict(kw, draft_net=_draft())
    if name == "mesh":
        from mxnet_tpu import parallel

        mesh = parallel.make_mesh({"tp": 2})
    with pytest.raises(mx.MXNetError) as exc:
        serving.GenerativeServer(
            net, ServerConfig(max_batch=2, max_length=64, min_length=8,
                              num_slots=2, **kw), mesh=mesh)
    assert says in str(exc.value)


# --- accounting: two kinds of state --------------------------------------------

def test_cache_bytes_report_both_kinds_and_match_the_planner(tiny):
    from mxnet_tpu.memory import plan_kv_pool

    net = tiny[0]
    srv = _server(net, num_slots=3, num_blocks=20)
    eng = srv.engine
    spec = eng.cache_spec
    assert (spec.kv_layers, spec.state_layers) == (2, 7)
    assert spec.layers == tuple(
        "state" if t == "conv" else "kv" for t in net.config.layer_types)
    by_kind = eng.kv_pool_bytes(by_kind=True)
    # K and V over the two attention layers only; 7 x (3, 64) a slot
    assert by_kind == {"kv_blocks": 2 * 2 * 20 * 2 * 4 * 16 * 4,
                       "slot_state": 7 * 3 * 3 * 64 * 4}
    assert eng.kv_pool_bytes() == sum(by_kind.values()) == plan_kv_pool(
        2, 2, 16, num_blocks=20, block_size=4, state_layers=7,
        state_shape=(3, 64), num_slots=3)
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    kv = st["kv_cache"]
    assert kv["state_bytes_per_slot"] == 7 * 3 * 64 * 4
    assert (st["kv_layers"], st["state_layers"]) == (2, 7)
    mgr = srv.replicas[0].mgr
    assert mgr.kv_bytes_per_block == 2 * 2 * 2 * 4 * 16 * 4
    mgr.admit("r", 5, 3)
    s2 = mgr.stats()
    assert s2["kv_block_bytes_in_use"] == 2 * mgr.kv_bytes_per_block
    assert s2["state_bytes_in_use"] == mgr.state_bytes_per_slot


# --- the lane log ---------------------------------------------------------------

def test_lane_log_carries_the_expert_counters(tiny):
    """The counters count the rows a request owns: a tick's are its slots
    that hold a block (never a vacant one), a prefill's the prompts' true
    lengths (never the padded end of a bucket)."""
    net = tiny[0]
    since = time.perf_counter()
    with _server(net, num_slots=2, max_batch=1) as srv:
        futs = [srv.submit(np.arange(1, 1 + n), max_new_tokens=4)
                for n in (3, 9, 12)]
        for f in futs:
            f.result(120)
        stats = srv.stats()
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert ticks and batches
    k, layers = 2, 8
    rows, alone = 0, 0
    for rec in ticks:
        n = round(rec["expert_rows_mean"] * rec["experts_touched"])
        rows += n
        # the stepped slots that hold a block: the active ones, and one
        # that is committed and not yet adopted
        assert n % (k * layers) == 0
        assert rec["n_active"] <= n // (k * layers) <= 2
        alone += n == k * layers
        assert 1 <= rec["expert_rows_max"] <= 2
        assert 0 < rec["experts_touched"] <= 8 * layers
    assert alone          # the last request decodes beside a vacant slot
    for rec, n_prompt in zip(batches, (3, 9, 12)):
        assert rec["n_tokens"] == n_prompt < rec["bucket"][1]
        n = n_prompt * k * layers
        rows += n
        assert rec["expert_rows_mean"] * rec["experts_touched"] \
            == pytest.approx(n)
    assert (ticks[0]["kv_layers"], ticks[0]["state_layers"]) == (2, 7)
    assert (batches[0]["kv_layers"], batches[0]["state_layers"]) == (2, 7)
    assert "kv_layers" not in ticks[-1]
    tot = stats["experts"]
    assert tot["rows"] == rows
    assert tot["programs"] == len(ticks) + len(batches)
    assert tot["experts_touched"] == sum(
        r["experts_touched"] for r in ticks + batches)


def test_a_llama_server_has_no_expert_fields():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    since = time.perf_counter()
    with serving.GenerativeServer(net, ServerConfig(
            max_batch=2, max_length=64, min_length=8, num_slots=2)) as srv:
        srv.generate(np.arange(1, 7), max_new_tokens=3)
        st = srv.stats()
    tick = tracing.lane_log("decode.tick", since=since)[0]
    assert "experts_touched" not in tick
    assert (tick["kv_layers"], tick["state_layers"]) == (2, 0)
    assert "experts" not in st and st["state_layers"] == 0


def test_compiled_program_names_are_the_benchmarks(tiny):
    """The new engine keeps ``jit__step_fn`` / ``_prefill_fn`` /
    ``_scatter_fn`` and one step signature."""
    import re

    eng = _server(tiny[0]).engine
    ids = np.ones((1, 8), np.int32)
    t0s = np.full(1, 6, np.int32)
    _toks, rows = eng.prefill_rows(ids, t0s)
    flat = np.full(2, eng.num_blocks, np.int32)
    lowered = {
        "step": eng._step.lower(eng._w, eng._pool, eng._dev(eng._tables),
                                eng._dev(eng._last), eng._toks,
                                eng._dev(eng._pos)),
        "prefill": eng._prefill.lower(eng._w, eng._dev(ids), eng._dev(t0s)),
        "scatter": eng._scatter.lower(eng._pool, rows, eng._dev(flat),
                                      eng._dev(np.zeros(1, np.int32))),
    }
    programs = _bench_module("families", "lfm2.py").Cell.programs
    for key, low in lowered.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)


# --- the benchmark's files -------------------------------------------------------

def test_decode_step_bytes_total_to_the_issues_table():
    fb = _bench_module("flops_bytes", "lfm2_decode_step.py")
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "lfm2_24b_a2b_l9.json")))
    emb = 65536 * 2048
    conv_op = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attn_op = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1536
    assert fb.expert_bytes(cfg) == 2 * expert == 18_874_368
    dense0 = conv_op + 3 * 2048 * 11776 + 2 * 2048
    moe = 2048 * 64 + 64 + 2 * 2048
    fixed = emb + 2048 + dense0 + 6 * (conv_op + moe) + 2 * (attn_op + moe)
    assert fb.fixed_weight_bytes(cfg) == 2 * fixed
    total = fb.fixed_weight_bytes(cfg) + 8 * 64 * fb.expert_bytes(cfg)
    assert fb.weight_bytes(cfg) == total
    assert total / 1e9 == pytest.approx(10.36, abs=0.01)
    assert fb.kv_bytes_per_token(cfg) == 4096           # 4 KiB a token
    assert fb.state_bytes_per_slot(cfg) == 7 * 3 * 2048 * 2
    # a tick: fixed weights, the touched experts once, the active slots'
    # embedding rows, K/V read at the true lengths plus the new row, the
    # states read and written
    need = fb.bytes_needed(cfg, active_slots=100, kv_tokens=30_000,
                           experts_touched=400)
    assert need == (fb.fixed_weight_bytes(cfg) + 400 * fb.expert_bytes(cfg)
                    + 100 * 2048 * 2 + (30_000 + 100) * 4096
                    + 2 * 100 * fb.state_bytes_per_slot(cfg))
    assert need < fb.weight_bytes(cfg)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "lfm2_24b_a2b_l9.json")))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == "LFM2-24B-A2B":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row.items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert changed == set(cfg["reduced"])
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b_l9"][0]
    assert set(entry["reduced"]) == changed
    assert cfg["num_hidden_layers"] == 9 == len(cfg["layer_types"])
    # the published order from layer 2 on, after one leading dense layer
    assert cfg["layer_types"][1:] == row["layer_types"][2:10]


@pytest.fixture
def harness(monkeypatch, tmp_path):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as harness

    # a traced run of its own trace directory: the checkout's one
    # ``.chipbench_trace`` is shared by every test process, and a traced
    # rehearsal that starts in another worker removes it under this one
    # ("the profiler wrote no trace")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    return harness


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_lfm2``: the
    new family, reference, traffic keys and readers at a tiny size."""
    data = os.path.join(BENCH, "tests", "data_lfm2")
    res = harness.run(["--workload", "tiny_lfm2.closed", "--seed", "4000000007",
                       "--seconds", "2", "--trace", str(trace),
                       "--control", "1"],
                      require_tpu=False, data_dir=data)
    out = capsys.readouterr().out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    compared = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            compared[name] = float(rest.split(" limit ")[0])
    chk = json.load(open(os.path.join(data, "traffic", "closed.json")))["check"]
    # the mean gap decides for a routed model; the widest is a reading
    assert chk["gap_limit"] is None and "served_logit_gap_max" in compared
    assert compared["served_logit_gap_mean"] <= chk["gap_mean_limit"] \
        < compared["control.served_logit_gap_mean"]
    # and a statistic that sees single tokens: the widest gap over the
    # tokens whose expert choice is not a near tie in any layer
    assert compared["served_logit_gap_max_steady"] <= chk["gap_steady_limit"] \
        < compared["control.served_logit_gap_max_steady"]
    assert compared["served_logit_gap_share_over_half"] \
        <= chk["gap_over_half_share_limit"] \
        < compared["control.served_logit_gap_share_over_half"]
    assert 0.5 < compared["steady_token_share"] <= 1.0
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report
        assert {"expert_rows_max_over_mean", "experts_touched_share",
                "decode_occupancy", "tick_host_ms"} <= set(res["metrics"])
        assert "moe_step_roofline" not in res["metrics"]
        assert res["metrics"]["expert_rows_max_over_mean"]["value"] >= 1.0
        assert 0 < res["metrics"]["experts_touched_share"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def test_the_references_choice_margin_is_the_last_chosen_over_the_first_left_out(
        ref):
    """``combine_weights``: the margin is read in score + bias, where the
    choice is made."""
    cfg = {"num_experts_per_tok": 2, "use_expert_bias": True,
           "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    u = jnp.eye(2, 4, dtype=jnp.float32)
    logit = np.array([[2.0, 1.0, 0.0, -1.0, -2.0],
                      [0.0, 0.0, 0.5, 0.5, -3.0]], np.float32)
    router = np.zeros((5, 4), np.float32)
    router[:, :2] = logit.T
    bias = np.array([0.0, 0.0, 0.0, 0.3, 0.0], np.float32)
    w = {"router": jnp.asarray(router), "expert_bias": jnp.asarray(bias)}
    comb, margin = ref.combine_weights(u, w, cfg, False)
    s = 1.0 / (1.0 + np.exp(-logit))
    pick = np.sort(s + bias, axis=-1)[:, ::-1]
    assert np.allclose(np.asarray(margin), pick[:, 1] - pick[:, 2], atol=1e-6)
    assert (np.asarray(comb) > 0).sum(-1).tolist() == [2, 2]
    # row 1: the bias lifts expert 3 over its equal, expert 2
    assert np.asarray(comb)[1, 3] > 0 and np.asarray(margin)[1] > 0


@pytest.mark.parametrize("wrong", [0, 1])
def test_one_wrong_token_among_many_fails_the_steady_row_not_the_mean(
        ref, tiny, wrong):
    """What the mean cannot see: one served token far off its reference, in a
    sample of a hundred.  The widest gap over the steady tokens sees it."""
    _net, _w, cfg = tiny
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 256, size=6) for _ in range(4)]
    ids = np.zeros((4, 32), np.int32)    # the reference's own greedy tokens
    ids[:, :6] = prompts
    for t in range(6, 31):
        rows = np.array([[i, t - 1] for i in range(4)], np.int32)
        ids[:, t] = ref.forward_rows(cfg, 3, ids, rows).argmax(-1)
    served = [ids[i, 6:31].copy() for i in range(4)]
    if wrong:
        served[2][-1] = (served[2][-1] + 97) % 256
    gaps, margin = ref.served_gaps(cfg, 3, prompts, served, 32, 100,
                                   with_margin=True)
    assert gaps.shape == margin.shape == (100,)
    steady = margin >= 1e-4
    assert steady.mean() > 0.9
    if wrong:
        assert gaps[steady].max() > 1.0 and gaps.mean() < 0.06
    else:
        assert gaps.max() == 0.0


def test_a_program_that_ignores_the_expert_bias_is_not_correct(harness, capsys,
                                                               monkeypatch):
    """The choice bias is seeded non-zero for this: routing without it sends
    tokens to other experts, and the mean gap passes its limit."""
    whole = lfm2.routed_ffn
    monkeypatch.setattr(
        lfm2, "routed_ffn",
        lambda *a, **kw: whole(*a, **dict(kw, choice_bias=None)))
    data = os.path.join(BENCH, "tests", "data_lfm2")
    res = harness.run(["--workload", "tiny_lfm2.closed", "--seed", "11",
                       "--seconds", "2", "--trace", "0"],
                      require_tpu=False, data_dir=data)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "served_logit_gap_mean" in out and "FAILED" in out

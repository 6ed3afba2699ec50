"""Qwen3-Next (``models/qwen3_next.py``, ``ops/gated_delta.py``) against its
plain reference (``chipbench/references/qwen3_next.py``) at a tiny size on the
CPU: each layer kind, the chunked scan against the token-by-token recurrence,
prefill then decode through ``GenerativeServer`` at every position, a state
layer that owns two arrays of two dtypes in the engine, the planner and the
lane log, the shares of a divided expert layer, the planted faults the
benchmark's check refuses, and a rehearsal of the benchmark's cell.

hidden 64, one period (three delta-rule layers of 2 key / 4 value heads of 16,
one attention layer of 4 / 2 heads of 16, 8 of which rotate), 16 experts, 4 a
token, 8 held, vocabulary 256; float32 weights.
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

from mxnet_tpu import nd, serving
from mxnet_tpu.models import qwen3_next as qn
from mxnet_tpu.models.decoder import Causal, CacheSpec, rope_tables
from mxnet_tpu.ops import gated_delta as gd
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
DATA = os.path.join(BENCH, "tests", "data_qwen3_next")
CELL_CONFIG = os.path.join(BENCH, "configs", "qwen3_next_80b_l8_ep4.json")


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_qwen3_next_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "qwen3_next.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
            "full_attention_interval": cfg.full_attention_interval,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.attn_head_dim,
            "partial_rotary_factor": cfg.rotary_dim / cfg.attn_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "router_experts": cfg.num_experts,
            "experts_held": list(cfg.experts_held),
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "shared_expert_intermediate_size":
                cfg.shared_expert_intermediate_size,
            "norm_topk_prob": cfg.norm_topk_prob, "vocab_size": cfg.vocab_size,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing, gates and attention are far from uniform), the
    norms' weights seeded too -> (net, the reference's weight tree)."""
    net = qn.qwen3_next_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    top["norm"] = 0.3 * jax.random.normal(key, top["norm"].shape)
    layers = []
    for l, lr in enumerate(net.layers):
        w = ref.init_layer(ref.layer_key(key, l), cfg, jnp.float32,
                           ref.layer_kind(cfg, l))
        assert sorted(w) == lr._names
        for i, n in enumerate(lr._names):
            if n.endswith("norm"):       # a norm that is not 1: (1 + w) shows
                w[n] = w[n] + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, 100 * l + i), w[n].shape)
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


def _ref_logits(ref, tiny, ids):
    _net, weights, cfg = tiny
    return np.asarray(ref.forward(cfg, weights, np.asarray(ids)))


def _rule_inputs(key, b, t, h, dk, dv):
    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    f32 = jnp.float32               # the suite runs under x64; the rule is f32
    return (unit(jax.random.normal(ks[0], (b, t, h, dk), f32)) * f32(dk ** -0.5),
            unit(jax.random.normal(ks[1], (b, t, h, dk), f32)),
            jax.random.normal(ks[2], (b, t, h, dv), f32),
            jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h), f32)),
            -jnp.exp(jax.random.uniform(ks[4], (b, t, h), f32, np.log(1e-3),
                                        np.log(0.3))))


# --- the rule's forms -----------------------------------------------------------

@pytest.mark.parametrize("t", [1, 5, 63, 64, 65, 130, 200])
def test_chunked_scan_equals_the_recurrence_at_any_length(t):
    """Lengths that are not multiples of the 64-row chunk: every row's output
    and the state after the last row."""
    x = _rule_inputs(jax.random.PRNGKey(t), 2, t, 3, 16, 8)
    o, s = gd.chunk_scan(*x)
    want_o, want_s = gd.recurrence(*x)
    assert o.shape == want_o.shape and s.shape == want_s.shape
    assert np.abs(o - want_o).max() < 1e-5 * max(1.0, np.abs(want_o).max())
    assert np.abs(s - want_s).max() < 1e-5 * max(1.0, np.abs(want_s).max())


@pytest.mark.parametrize("t0", [1, 23, 64, 97])
def test_scan_of_a_padded_bucket_returns_the_state_of_the_true_length(t0):
    x = _rule_inputs(jax.random.PRNGKey(7), 1, 128, 3, 16, 8)
    live = jnp.arange(128)[None] < t0
    o, s = gd.chunk_scan(*x, live=live)
    want_o, want_s = gd.recurrence(*(a[:, :t0] for a in x))
    assert np.abs(o[:, :t0] - want_o).max() < 1e-5
    assert np.abs(s - want_s).max() < 1e-5
    # and with the padded rows let in, the state is another
    _o, moved = gd.chunk_scan(*x)
    assert t0 == 128 or np.abs(moved - want_s).max() > 1e-3


def test_scan_carries_a_state_it_was_given():
    x = _rule_inputs(jax.random.PRNGKey(9), 2, 100, 2, 16, 8)
    _o, mid = gd.recurrence(*(a[:, :37] for a in x))
    o, s = gd.chunk_scan(*(a[:, 37:] for a in x), s0=mid)
    want_o, want_s = gd.recurrence(*x)
    assert np.abs(o - want_o[:, 37:]).max() < 1e-5
    assert np.abs(s - want_s).max() < 1e-5


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_step_is_the_recurrences_one_token_and_leaves_other_slots_alone(form):
    """The step over a pool, slot by slot: one token of the recurrence for the
    slots the step owns, the state untouched to the bit for the others.  The
    Pallas kernel runs in the interpreter at heads of 128 x 128."""
    h, dk, dv = (8, 128, 128) if form == "kernel" else (3, 16, 8)
    x = _rule_inputs(jax.random.PRNGKey(4), 5, 1, h, dk, dv)
    x = tuple(a[:, 0] for a in x)
    pool = jax.random.normal(jax.random.PRNGKey(5), (5, h, dk, dv), jnp.float32)
    live = jnp.asarray([True, False, True, True, False])
    o, s = gd.step(pool, *x, live=live, kernel=form == "kernel",
                   interpret=form == "kernel")
    for slot in range(5):
        one = tuple(a[slot:slot + 1, None] for a in x)
        want_o, want_s = gd.recurrence(*one, s0=pool[slot:slot + 1])
        if live[slot]:
            assert np.abs(o[slot] - want_o[0, 0]).max() < 1e-5
            assert np.abs(s[slot] - want_s[0]).max() < 1e-5
        else:
            assert np.array_equal(np.asarray(s[slot]), np.asarray(pool[slot]))


def test_the_kernel_is_chosen_from_platform_mesh_and_shapes():
    assert gd.step_applicable("tpu", None, 32, 128, 128)
    assert not gd.step_applicable("cpu", None, 32, 128, 128)
    assert not gd.step_applicable("tpu", object(), 32, 128, 128)
    assert not gd.step_applicable("tpu", None, 32, 64, 128)
    assert gd.step_form((32, 128, 128)) == "step_xla"      # a CPU here


def test_kernel_compiles_for_the_chip_in_place_at_the_published_sizes():
    """256 slots of 32 heads of (128, 128) float32 under donation, compiled for
    a described v5e: one Mosaic call, the pool aliased, no pool-sized
    temporary."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                    # noqa: BLE001
        pytest.skip(f"no v5e topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    n, h = 256, 32
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # as the chip runs it: 32-bit index arithmetic (the suite turns x64 on)
        with jax.enable_x64(False):
            comp = jax.jit(
                lambda pool, q, k, v, b, g: gd.step(pool, q, k, v, b, g,
                                                    kernel=True),
                donate_argnums=0).lower(
                    sd(n, h, 128, 128), sd(n, h, 128), sd(n, h, 128),
                    sd(n, h, 128), sd(n, h), sd(n, h)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    text = comp.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%gated_delta_step" in text
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == n * h * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 2 ** 20


# --- each layer kind against the reference --------------------------------------

def test_gluon_forward_equals_reference_logits(ref, tiny):
    net = tiny[0]
    seq = np.random.RandomState(0).randint(1, 256, size=40)
    got = net(nd.array(seq[None], dtype="int32")).asnumpy()[0]
    want = _ref_logits(ref, tiny, seq)
    assert got.shape == want.shape == (40, 256)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("l,kind", [(0, "delta"), (3, "attention")])
def test_each_layer_kind_equals_the_references_layer(ref, tiny, l, kind):
    net, weights, cfg = tiny
    assert ref.layer_kind(cfg, l) == kind
    assert net.config.is_linear(l) == (kind == "delta")
    x = jnp.asarray(np.random.RandomState(l).randn(1, 21, 64), jnp.float32)
    cos, sin = rope_tables(21, net.config.head_dim, net.config.rope_theta)
    got, _kept, counts = qn.Qwen3NextMath(net.config).layer(
        weights["layers"][l], x, (cos[None, None], sin[None, None]),
        Causal(21))
    with jax.default_matmul_precision("highest"):
        want = ref.layer_forward(x[0], weights["layers"][l], cfg, kind)
    assert np.abs(got[0] - want).max() < 2e-5 * np.abs(want).max()
    assert int(counts.sum()) == 21 * 4 and counts.shape == (16,)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, tiny):
    """Four chips hold four experts each; every share carries the shared
    expert, which a deployment counts once."""
    net, weights, cfg = tiny
    w = dict(weights["layers"][0])
    u = jnp.asarray(np.random.RandomState(3).randn(30, 64), jnp.float32)
    key = ref.layer_key(jax.random.PRNGKey(3), 0)
    whole = dict(cfg, experts_held=[0, 16])
    w.update(ref.init_experts(key, whole, jnp.float32, 0, 16))

    def ffn(first, count):
        conf = qn.Qwen3NextConfig(**dict(
            qn.QWEN3_NEXT_CONFIGS["qwen3_next_tiny"],
            experts_held=(first, count)))
        p = dict(w, **{n: w[n][first:first + count]
                       for n in ("w_gate", "w_up", "w_down")})
        return qn.Qwen3NextMath(conf).ffn(p, u)

    uncut, counts = ffn(0, 16)
    shares = [ffn(first, 4)[0] for first in (0, 4, 8, 12)]
    shared = qn._swiglu(u, w["shared_gate"], w["shared_up"], w["shared_down"]) \
        * jax.nn.sigmoid(u @ w["shared_expert_gate"].T)
    assert np.abs(sum(shares) - 3 * shared - uncut).max() \
        < 1e-5 * np.abs(uncut).max()
    assert np.abs(shares[0] - shared).max() > 1e-3      # a share routes
    # and the uncut layer is the reference's
    with jax.default_matmul_precision("highest"):
        comb, _m = ref.combine_weights(u, w, whole)
        want = ref.experts_part(u, comb, w) + shared
    assert np.abs(uncut - want).max() < 2e-5 * np.abs(want).max()
    assert int(counts.sum()) == 30 * 4


# --- through GenerativeServer -------------------------------------------------

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
    rows, lg, _c = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                          jnp.asarray([t0]))
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    return rows, np.asarray(lg)[0]


def _teacher_forced_logits(eng, seq, t0, slot=0):
    """Prefill ``seq[:t0]`` (the chunked scan in a padded bucket), hand the
    states and K/V over, then decode the rest of ``seq`` token by token
    through the engine's own programs -> (len(seq) - t0 + 1, vocab)."""
    dec, w = eng._dec, eng._w
    out = [_prefill_and_commit(eng, seq, t0, slot)[1]]
    for t in range(t0, len(seq)):
        ids_t = np.zeros(eng.num_slots, np.int32)
        pos = np.zeros(eng.num_slots, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        lg, eng._pool, _c = dec._step_blocks_impl(
            w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(ids_t),
            jnp.asarray(pos))
        out.append(np.asarray(lg)[slot])
    return np.stack(out)


@pytest.mark.parametrize("t0", [1, 2, 5, 13, 20])
def test_prefill_then_decode_equals_reference_at_every_position(ref, tiny, t0):
    """Ragged prompts shorter and longer than a bucket, and shorter than the
    conv's four taps.  Tolerance: float32 on both sides; the chunked scan sums
    in another order than the reference's token-by-token pass and the served
    products run at the CPU's default precision, the reference's at
    ``highest``: 5e-4 of the largest logit, a hundred times below what a lost
    state or a stale ring row moves (the planted tests below)."""
    eng = _server(tiny[0]).engine
    seq = np.random.RandomState(100 + t0).randint(1, 256, size=t0 + 9)
    got = _teacher_forced_logits(eng, seq, t0)
    want = _ref_logits(ref, tiny, seq)[t0 - 1:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 5e-4 * np.abs(want).max()


@pytest.mark.parametrize("t0", [1, 2, 3, 7, 16])
def test_prefill_hands_on_both_arrays_at_the_true_length(tiny, t0):
    """A prompt padded to a bucket: the ring is the convolution's input at
    t0-3 .. t0-1 (row t % 3), zeros where the prompt is shorter; the recurrent
    state is the one the UNPADDED prompt leaves."""
    net = tiny[0]
    eng = _server(net).engine
    seq = np.random.RandomState(t0).randint(1, 256, size=t0)
    rows, _lg = _prefill_and_commit(eng, seq, t0)
    ring, state = rows[0]
    assert ring.shape == (1, 3, net.config.conv_dim) and ring.dtype == jnp.float32
    assert state.shape == (1, 4, 16, 16) and state.dtype == jnp.float32
    dec, w = eng._dec, eng._w
    exact, _lg, _c = dec._prefill_rows_impl(w, jnp.asarray(seq[None]),
                                            jnp.asarray([t0]))
    assert np.abs(state - exact[0][1]).max() < 1e-5
    p = w["layers"][0]
    mixed = (qn._norm(w["emb"][jnp.asarray(seq)], p["op_norm"], 1e-6)
             @ p["in_qkvz"].T)[:, :net.config.conv_dim]
    for r in range(3):
        src = [q for q in range(t0 - 3, t0) if q % 3 == r][0]
        want = np.zeros(net.config.conv_dim) if src < 0 else np.asarray(mixed[src])
        assert np.allclose(ring[0, r], want, atol=1e-5), (r, src)
    # and the engine's pool holds them in the slot, each in its own dtype
    held_ring, held_state = eng._pool[0]
    assert np.array_equal(np.asarray(held_state[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(held_ring[0]), np.asarray(ring[0]))


def test_served_tokens_follow_the_reference(ref, tiny):
    """Through the lanes: every generated token is the reference's argmax
    given what came before, for prompts of ragged lengths in one batch."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 256, size=n) for n in (1, 3, 9, 17)]
    with _server(tiny[0]) as srv:
        futs = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = [f.result(120) for f in futs]
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 6
        lg = _ref_logits(ref, tiny, o)
        for j in range(6):
            row = lg[len(p) - 1 + j]
            assert row[o[len(p) + j]] >= row.max() - 1e-3 * np.abs(row).max()


def test_freed_slot_readmitted_gives_a_fresh_servers_logits(tiny):
    """One slot: the second request reuses the first's slot; admission writes
    both state arrays whole, so nothing of the first is left."""
    net = tiny[0]
    rs = np.random.RandomState(9)
    first, second = rs.randint(1, 256, size=19), rs.randint(1, 256, size=12)
    used = _server(net, num_slots=1).engine
    _teacher_forced_logits(used, first, 11)
    used.clear_slot(0)
    got = _teacher_forced_logits(used, second, 2)
    fresh = _teacher_forced_logits(_server(net, num_slots=1).engine, second, 2)
    assert np.array_equal(got, fresh)


def test_a_slot_committed_and_not_yet_adopted_keeps_its_state(tiny):
    """A step is not idempotent: a neighbour's tick must not move a committed
    slot's state on before the decode lane owns it."""
    eng = _server(tiny[0]).engine
    seq = np.random.RandomState(2).randint(1, 256, size=9)
    _prefill_and_commit(eng, seq, 5, slot=1)
    before = [np.asarray(a) for a in eng._pool[0]]
    eng.step([])                    # a tick that owns nothing
    for a, b in zip(before, eng._pool[0]):
        assert np.array_equal(a, np.asarray(b))
    eng.step([1])                   # and one that owns the slot
    assert np.abs(before[1][1] - np.asarray(eng._pool[0][1][1])).max() > 1e-4
    assert np.array_equal(before[1][0], np.asarray(eng._pool[0][1][0]))


# --- what is refused, loudly ---------------------------------------------------

@pytest.mark.parametrize("name,kw,says", [
    ("radix", dict(radix_cache=True), "snapshot"),
    ("int8", dict(int8=True), "int8=True"),
])
def test_options_refused_as_for_any_model_with_per_slot_state(tiny, name, kw,
                                                              says):
    import mxnet_tpu as mx

    with pytest.raises(mx.MXNetError) as exc:
        serving.GenerativeServer(
            tiny[0], ServerConfig(max_batch=2, max_length=64, min_length=8,
                                  num_slots=2, **kw))
    assert says in str(exc.value)


# --- accounting: a state layer with two arrays of two dtypes ----------------------

def test_state_pool_bytes_equal_the_planners_with_a_float32_array_in_it():
    """bfloat16 weights: the ring is priced at 2 bytes, the state at 4."""
    from mxnet_tpu.memory import plan_kv_pool

    net = qn.qwen3_next_tiny()
    net.cast("bfloat16")
    net.initialize()
    srv = _server(net, num_slots=3, num_blocks=20)
    eng, spec = srv.engine, srv.engine.cache_spec
    assert spec.layers == ("state", "state", "state", "kv")
    conv = net.config.conv_dim
    assert spec.state_arrays == (((3, conv), None),
                                 ((4, 16, 16), np.dtype("float32")))
    ring, state = eng._pool[0]
    assert (ring.dtype, state.dtype) == (jnp.bfloat16, jnp.float32)
    assert spec.state_array_bytes(2) == (3 * conv * 2, 4 * 16 * 16 * 4)
    per_slot = 3 * (3 * conv * 2 + 4 * 16 * 16 * 4)
    assert spec.state_bytes_per_slot(2) == per_slot
    by_kind = eng.kv_pool_bytes(by_kind=True)
    assert by_kind == {
        "kv_blocks": 2 * 20 * 2 * 4 * 16 * 2, "slot_state": 3 * per_slot,
        "slot_state_arrays": (3 * 3 * 3 * conv * 2, 3 * 3 * 4 * 16 * 16 * 4)}
    assert eng.kv_pool_bytes() == sum(
        by_kind[k] for k in ("kv_blocks", "slot_state")) == plan_kv_pool(
        1, 2, 16, num_blocks=20, block_size=4, dtype="bfloat16",
        state_layers=3, state_arrays=spec.state_arrays, num_slots=3)
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    assert st["cache_bytes"] == by_kind
    assert st["kv_cache"]["state_bytes_per_slot"] == per_slot
    assert (st["kv_layers"], st["state_layers"]) == (1, 3)
    assert st["linear_attention"] == "step_xla"
    assert st["experts_held"] == (0, 8)


def test_one_array_spec_reads_as_before():
    """LFM2's spec is the one-array case: the same bytes, a bare array."""
    spec = CacheSpec(("state", "kv"), 2, 16, state_shape=(3, 64))
    assert spec.state_arrays == (((3, 64), None),)
    assert spec.state_bytes_per_slot(2) == 3 * 64 * 2
    one = object()
    assert spec.state_entry([one]) is one
    assert spec.entry_arrays(one) == (one,)
    assert spec.entry_arrays(spec.state_entry([one, one])) == (one, one)


# --- the lane log ---------------------------------------------------------------

def test_lane_log_carries_the_states_bytes_and_the_scans_rows(tiny):
    net = tiny[0]
    since = time.perf_counter()
    with _server(net, num_slots=2, max_batch=1) as srv:
        futs = [srv.submit(np.arange(1, 1 + n), max_new_tokens=4)
                for n in (3, 9, 12)]
        for f in futs:
            f.result(120)
        per_slot = srv.stats()["kv_cache"]["state_bytes_per_slot"]
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert ticks and len(batches) == 3
    for rec in ticks:
        # read and written once, the active slots' alone
        assert rec["state_bytes"] == 2 * per_slot * rec["n_active"] > 0
        # 8 of 16 experts held: the touched ones among them
        assert 0 < rec["experts_touched_held"] <= min(
            rec["experts_touched"], 4 * 8)
    for rec, n in zip(batches, (3, 9, 12)):
        assert rec["scan_rows"] == n == rec["n_tokens"]
        assert rec["scan_rows_padded"] == rec["bucket"][0] * rec["bucket"][1]
        assert rec["scan_rows_padded"] > n
    assert ticks[0]["linear_attention"] == "step_xla"
    assert batches[0]["linear_attention"] == "step_xla"
    assert "linear_attention" not in ticks[-1]


def test_a_llama_server_has_no_state_fields():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    since = time.perf_counter()
    with serving.GenerativeServer(net, ServerConfig(
            max_batch=2, max_length=64, min_length=8, num_slots=2)) as srv:
        srv.generate(np.arange(1, 7), max_new_tokens=3)
        st = srv.stats()
    tick = tracing.lane_log("decode.tick", since=since)[0]
    batch = tracing.lane_log("prefill.batch", since=since)[0]
    assert "state_bytes" not in tick and "linear_attention" not in tick
    assert "scan_rows" not in batch
    assert st["linear_attention"] is None
    assert "slot_state_arrays" not in st["cache_bytes"]


def test_compiled_program_names_are_the_benchmarks(tiny):
    import re

    eng = _server(tiny[0]).engine
    ids, t0s = np.ones((1, 8), np.int32), np.full(1, 6, np.int32)
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
    programs = _bench_module("families", "qwen3_next.py").Cell.programs
    for key, low in lowered.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)
    text = lowered["step"].as_text(debug_info=True)
    assert "gated_delta_step" in text and "delta_project" in text
    assert "gated_delta_chunk_scan" in lowered["prefill"].as_text(
        debug_info=True)


# --- the benchmark's files -------------------------------------------------------

def test_decode_step_bytes_total_to_the_issues_table():
    fb = _bench_module("flops_bytes", "qwen3_next_decode_step.py")
    cfg = json.load(open(CELL_CONFIG))
    delta, attn = fb.operator_params(cfg)
    small = 32 + 32 + 128                          # A_log, dt_bias, the norm
    assert delta == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 + small
    assert delta / 1e6 == pytest.approx(33.72, abs=0.01)
    assert attn == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert attn / 1e6 == pytest.approx(27.26, abs=0.01)
    assert fb.expert_params(cfg) == 3 * 2048 * 512
    assert fb.expert_bytes(cfg) == 6_291_456
    assert (512 * 2048) / 1e6 == pytest.approx(1.05, abs=0.01)        # router
    assert fb.layer_fixed_params(cfg) == 2 * 2048 + 512 * 2048 \
        + 3 * 2048 * 512 + 2048
    d_layer, a_layer = fb.layer_params(cfg)
    assert d_layer / 1e6 == pytest.approx(440.6, abs=0.1)
    assert a_layer / 1e6 == pytest.approx(434.1, abs=0.1)
    assert (3 * d_layer + a_layer) / 1e6 == pytest.approx(1756, abs=1)
    total = fb.weight_bytes(cfg)
    assert total / 2 == 2 * (3 * d_layer + a_layer) + 2 * 37984 * 2048 + 2048
    assert total / 2e6 == pytest.approx(3668, abs=1)              # parameters
    assert total / 1e9 == pytest.approx(7.34, abs=0.01)
    assert 8 * 128 * fb.expert_bytes(cfg) / 1e9 == pytest.approx(6.44, abs=0.01)
    assert fb.kv_bytes_per_token(cfg) == 4096                     # 4 KiB
    assert fb.recurrent_bytes_per_slot(cfg) == 2 * 2 ** 20        # 2 MiB
    assert fb.state_bytes_per_slot(cfg) == 6 * (2 * 2 ** 20 + 48 * 1024)
    assert fb.state_bytes_per_slot(cfg) / 2 ** 20 == pytest.approx(12.3, abs=0.05)
    # a tick at 256 full slots: every held expert touched
    state = 2 * 256 * fb.state_bytes_per_slot(cfg)
    need = fb.bytes_needed(cfg, active_slots=256, kv_tokens=256 * 800,
                           experts_touched=8 * 128)
    assert need == (fb.fixed_weight_bytes(cfg) + 1024 * fb.expert_bytes(cfg)
                    + 256 * 2048 * 2 + (256 * 800 + 256) * 4096 + state)
    assert fb.bytes_needed(cfg, 256, 256 * 800, 1024, state_bytes=state) == need
    assert state / 1e9 == pytest.approx(6.6, abs=0.05)
    assert 100 * state / need == pytest.approx(45, abs=2)
    assert need / 819e9 * 1e3 == pytest.approx(17.8, abs=0.5)     # ms a tick
    # operations: 2.5 routed experts a row lie here; far below the bytes' time
    flops = fb.flops_needed(cfg, 256, 256 * 800)
    assert flops / 197e12 < 0.1 * need / 819e9
    per_row = 6 * delta + 2 * attn + 8 * (512 * 2048 + 3 * 2048 * 512 + 2048
                                          + 2.5 * 3 * 2048 * 512) \
        + 37984 * 2048 + 6 * 3 * 32 * 128 * 128
    assert flops == 2 * (256 * per_row + 2 * 2 * 16 * 256 * 256 * 800)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(CELL_CONFIG))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == "Qwen3-Next-80B-A3B-Instruct":
                row = json.loads(line)
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert changed == set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["source"] == row["source_url"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "qwen3_next_80b_l8_ep4"][0]
    assert set(entry["reduced"]) == changed
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 128]
    assert cfg["router_experts"] == 512 and cfg["vocab_size"] * 4 == 151936
    cell = [w for w in bench["workloads"]
            if w["name"] == "qwen3_next.chat_decode_sat"][0]
    assert (cell["config"], cell["chips"]) == ("qwen3_next_80b_l8_ep4", 1)
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")))
    assert (mix["clients"], mix["system"]["num_slots"]) == (512, 256)
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert mix["system"]["max_length"] == 1536 == mix["check"]["pad_tokens"]


def test_the_program_is_built_at_the_published_widths():
    """The family's constructor call, with the cell's configuration: shapes
    only (nothing is allocated)."""
    cfg = json.load(open(CELL_CONFIG))
    conf = qn.Qwen3NextConfig(
        num_layers=cfg["num_hidden_layers"], num_experts=cfg["router_experts"],
        experts_held=tuple(cfg["experts_held"]), vocab_size=cfg["vocab_size"])
    shapes = [qn._layer_param_shapes(conf, l) for l in range(8)]
    fb = _bench_module("flops_bytes", "qwen3_next_decode_step.py")
    got = sum(int(np.prod(s)) for layer in shapes for s in layer.values())
    d_layer, a_layer = fb.layer_params(cfg)
    assert got == 6 * d_layer + 2 * a_layer
    assert [conf.is_linear(l) for l in range(8)] == [True] * 3 + [False] \
        + [True] * 3 + [False]
    assert conf.state_arrays() == (((3, 8192), None),
                                   ((32, 128, 128), "float32"))
    assert (conf.rotary_dim, conf.attn_head_dim) == (64, 256)


# --- the cell, rehearsed, and what its check refuses ------------------------------

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


def _compared(out):
    compared = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            compared[name] = float(rest.split(" limit ")[0])
    return compared


def _limits():
    return json.load(open(os.path.join(DATA, "traffic", "closed.json")))["check"]


LIMIT_ROWS = [("served_logit_gap_mean", "gap_mean_limit"),
              ("served_logit_gap_max_steady", "gap_steady_limit"),
              ("served_logit_gap_share_over_0.5", "gap_share_limit")]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_qwen3_next``:
    the new family, reference, traffic keys and readers at a tiny size, the
    second quarter of the experts held (8 of 16 from the 5th).  What is held
    here does not depend on how many ticks the machine's load lets into the
    window: the readers' values are ratios of counters."""
    res = harness.run(["--workload", "tiny_qwen3_next.closed", "--seed",
                       "4000000007", "--seconds", "2", "--trace", str(trace),
                       "--control", "1"], require_tpu=False, data_dir=DATA)
    compared = _compared(capsys.readouterr().out)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    chk = _limits()
    assert chk["gap_limit"] is None and "served_logit_gap_max" in compared
    for row, key in LIMIT_ROWS:
        assert compared[row] <= chk[key] < compared["control." + row], row
    # the float8 reference is refused, and so is a reference that decodes
    # from a zero recurrent state after the prompt
    assert compared["control.passes_every_limit"] == 0
    assert compared["control_state.passes_every_limit"] == 0
    assert compared["control_state.served_logit_gap_mean"] \
        > chk["gap_mean_limit"]
    assert 0.5 < compared["steady_token_share"] <= 1.0
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report where the window held a tick
        assert not {"linear_step_roofline", "gated_delta_step_roofline",
                    "decode_step_ms"} & set(res["metrics"])
        share = res["metrics"].get("state_bytes_share")
        assert share is None or 0 < share["value"] < 100
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def _run_planted(harness, capsys):
    res = harness.run(["--workload", "tiny_qwen3_next.closed", "--seed", "11",
                       "--seconds", "2", "--trace", "0", "--control", "0"],
                      require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "FAILED" in out
    return _compared(out)


def test_a_state_not_handed_over_is_not_correct(harness, capsys, monkeypatch):
    """Planted: the prefill's recurrent state does not reach the slot, which
    decodes from zeros (the ring arrives)."""
    whole = qn.Qwen3NextDecoder._sequence_state

    def lost(self, kept, t0):
        ring, state = whole(self, kept, t0)
        return ring, jnp.zeros_like(state)

    monkeypatch.setattr(qn.Qwen3NextDecoder, "_sequence_state", lost)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > 10 * _limits()["gap_mean_limit"]


def test_a_padded_row_that_moves_the_state_is_not_correct(harness, capsys,
                                                          monkeypatch):
    """Planted: the scan lets the padded end of a bucket into the state."""
    whole = gd.chunk_scan
    monkeypatch.setattr(gd, "chunk_scan",
                        lambda *a, live=None, **kw: whole(*a, **kw))
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]


def test_the_shared_experts_gate_left_out_is_not_correct(harness, capsys,
                                                         monkeypatch):
    """Planted: the shared expert is added whole, not times its sigmoid."""
    real = jax.nn.sigmoid

    def ungated(self, p, u, live=None):
        y, counts = qn.routed_ffn(
            u.reshape(-1, u.shape[-1]), p["router"], p["w_gate"], p["w_up"],
            p["w_down"], self.cfg.num_experts_per_tok, score="softmax",
            renormalize=True, experts_held=self.cfg.experts_held,
            live=None if live is None else live.reshape(-1))
        return y.reshape(u.shape) + qn._swiglu(
            u, p["shared_gate"], p["shared_up"], p["shared_down"]), counts

    assert real is jax.nn.sigmoid
    monkeypatch.setattr(qn.Qwen3NextMath, "ffn", ungated)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]


def test_a_conv_ring_one_row_stale_is_not_correct(harness, capsys, monkeypatch):
    """Planted: the ring handed over is the one of a prompt a token shorter."""
    whole = qn.Qwen3NextDecoder._sequence_state

    def stale(self, kept, t0):
        ring, _state = whole(self, kept, jnp.maximum(t0 - 1, 0))
        return ring, kept[1]

    monkeypatch.setattr(qn.Qwen3NextDecoder, "_sequence_state", stale)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]

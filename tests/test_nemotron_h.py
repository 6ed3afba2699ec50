"""Nemotron-H (``models/nemotron_h.py``, ``ops/ssm_scan.py``) against its plain
reference (``chipbench/references/nemotron_h.py``) at a tiny size on the CPU:
each layer kind, the chunked scan against the token-by-token recurrence,
prefill then decode through ``GenerativeServer`` at every position, a served
layer that is a mixer and the expert part behind it, the shares of a divided
latent expert layer, the planted faults the benchmark's check refuses, and a
rehearsal of the benchmark's cell.

hidden 64, pattern ``MEM*E`` (served layers ``ME``, ``M``, ``*E``): 4
state-space heads of 8 with a state of 16 in 2 groups, 4 / 2 attention heads of
16, 16 experts of 32 in a latent 32, 4 a token, 8 held, a shared expert of 64,
vocabulary 256; float32 weights.
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
from mxnet_tpu.models import nemotron_h as nh
from mxnet_tpu.models.decoder import Causal, rms_norm
from mxnet_tpu.ops import ssm_scan as ss
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
DATA = os.path.join(BENCH, "tests", "data_nemotron_h")
CELL_CONFIG = os.path.join(BENCH, "configs",
                           "nemotron3_super_120b_l11_ep4.json")


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_nemotron_h_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "nemotron_h.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "hybrid_override_pattern": cfg.pattern,
            "mamba_num_heads": cfg.mamba_num_heads,
            "mamba_head_dim": cfg.mamba_head_dim,
            "ssm_state_size": cfg.ssm_state_size, "n_groups": cfg.n_groups,
            "conv_kernel": cfg.conv_kernel,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.attn_head_dim, "norm_eps": cfg.norm_eps,
            "router_experts": cfg.num_experts,
            "experts_held": list(cfg.experts_held),
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "moe_latent_size": cfg.moe_latent_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "moe_shared_expert_intermediate_size":
                cfg.shared_expert_intermediate_size,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "time_step_min": 0.001, "time_step_max": 0.1,
            "time_step_floor": 1e-4, "vocab_size": cfg.vocab_size,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _letters(cfg):
    """Served layer -> the pattern positions it holds."""
    out, i = [], 0
    for _m, e in cfg.units:
        out.append((i, i + 1) if e else (i,))
        i += 1 + e
    return out


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing, gates and attention are far from uniform), the
    norms' weights and the choice bias seeded too -> (net, the reference's
    weight tree, a layer a LETTER of the pattern)."""
    net = nh.nemotron_h_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    top["norm"] = 1 + 0.3 * jax.random.normal(key, top["norm"].shape)
    letters = []
    for l in range(len(net.config.pattern)):
        w = ref.init_layer(ref.layer_key(key, l), cfg, jnp.float32,
                           ref.layer_kind(cfg, l))
        for i, n in enumerate(sorted(w)):
            if n.endswith("norm") or n == "expert_bias":   # not 1, not 0
                w[n] = w[n] + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, 100 * l + i), w[n].shape)
        letters.append(w)
    for lr, at in zip(net.layers, _letters(net.config)):
        w = {n: a for l in at for n, a in letters[l].items()}
        assert sorted(w) == lr._names
        for n in lr._names:
            getattr(lr, n).set_data(nd.NDArray(w[n]))
    net.embed_tokens.weight.set_data(nd.NDArray(top["emb"]))
    net.norm.weight.set_data(nd.NDArray(top["norm"]))
    net.lm_head.weight.set_data(nd.NDArray(top["head"]))
    return net, {"top": top, "layers": letters}


@pytest.fixture(scope="module")
def tiny(ref):
    net, weights = _net_and_weights(ref)
    return net, weights, _ref_cfg(net.config)


def _ref_logits(ref, tiny, ids):
    _net, weights, cfg = tiny
    return np.asarray(ref.forward(cfg, weights, np.asarray(ids)))


def _scan_inputs(key, b, t, h, p, g, n):
    ks = jax.random.split(key, 6)
    f32 = jnp.float32               # the suite runs under x64; the scan is f32
    return (jax.random.normal(ks[0], (b, t, h, p), f32),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h), f32) - 2),
            -jnp.exp(jax.random.uniform(ks[2], (h,), f32, 0, np.log(16.0))),
            jax.random.normal(ks[3], (b, t, g, n), f32),
            jax.random.normal(ks[4], (b, t, g, n), f32),
            jax.random.normal(ks[5], (h,), f32))


# --- the recurrence's forms -----------------------------------------------------

@pytest.mark.parametrize("t", [1, 5, 63, 64, 65, 130, 200])
def test_chunked_scan_equals_the_recurrence_at_any_length(t):
    a = _scan_inputs(jax.random.PRNGKey(t), 2, t, 4, 8, 2, 16)
    want_y, want_s = ss.recurrence(*a)
    got_y, got_s = ss.chunk_scan(*a, chunk=64)
    assert got_y.shape == (2, t, 4, 8) and got_s.shape == (2, 4, 8, 16)
    assert np.abs(got_y - want_y).max() < 1e-4 * max(1, np.abs(want_y).max())
    assert np.abs(got_s - want_s).max() < 1e-4 * max(1, np.abs(want_s).max())


@pytest.mark.parametrize("t0", [1, 3, 17, 64, 77])
def test_scan_of_a_padded_bucket_returns_the_state_of_the_true_length(t0):
    """Rows past a sequence's true length enter with dt = 0: the state that
    comes back is the one the unpadded sequence leaves, each row its own."""
    a = _scan_inputs(jax.random.PRNGKey(7), 2, 96, 4, 8, 2, 16)
    lens = jnp.asarray([t0, 96 - t0])
    live = jnp.arange(96)[None] < lens[:, None]
    _y, got = ss.chunk_scan(*a, live=live, chunk=32)
    x, dt, A, B, C, D = a
    for r, n in enumerate((t0, 96 - t0)):
        _y, want = ss.recurrence(x[r:r + 1, :n], dt[r:r + 1, :n], A,
                                 B[r:r + 1, :n], C[r:r + 1, :n], D)
        assert np.abs(got[r] - want[0]).max() < 1e-4


def test_scan_carries_a_state_it_was_given():
    a = _scan_inputs(jax.random.PRNGKey(8), 1, 100, 4, 8, 2, 16)
    x, dt, A, B, C, D = a
    cut = lambda lo, hi: (x[:, lo:hi], dt[:, lo:hi], A, B[:, lo:hi],  # noqa: E731
                          C[:, lo:hi], D)
    want_y, want_s = ss.recurrence(*a)
    y0, s0 = ss.chunk_scan(*cut(0, 37), chunk=16)
    y1, s1 = ss.chunk_scan(*cut(37, 100), s0=s0, chunk=16)
    assert np.abs(jnp.concatenate([y0, y1], 1) - want_y).max() < 1e-4
    assert np.abs(s1 - want_s).max() < 1e-4


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_step_is_the_recurrences_one_token_and_leaves_other_slots_alone(form):
    """The step over a pool, slot by slot: one token of the recurrence for the
    slots the step owns, the state untouched to the bit for the others.  The
    Pallas kernel runs in the interpreter at heads of (64, 128), a group of 16
    heads a block; the pool in its stored layout, two heads of 64 a lane row
    (four of 8 at the tiny sizes)."""
    h, p, g, n = (32, 64, 2, 128) if form == "kernel" else (12, 8, 3, 16)
    x, dt, A, B, C, D = _scan_inputs(jax.random.PRNGKey(4), 5, 1, h, p, g, n)
    plain = jax.random.normal(jax.random.PRNGKey(5), (5, h, p, n), jnp.float32)
    pool = ss.to_stored(plain, g)
    assert pool.shape == (5,) + ss.state_shape(h, p, n, g)
    assert ss.lane_pack(h, p, g) == (2 if form == "kernel" else 4)
    assert np.array_equal(np.asarray(ss.from_stored(pool, p)),
                          np.asarray(plain))
    live = jnp.asarray([True, False, True, True, False])
    y, s = ss.step(pool, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, live=live,
                   kernel=form == "kernel", interpret=form == "kernel")
    for slot in range(5):
        one = slice(slot, slot + 1)
        want_y, want_s = ss.recurrence(x[one], dt[one], A, B[one], C[one], D,
                                       s0=plain[one])
        if live[slot]:
            assert np.abs(y[slot] - want_y[0, 0]).max() < 1e-5
            assert np.abs(ss.from_stored(s[slot], p) - want_s[0]).max() < 1e-5
        else:
            assert np.array_equal(np.asarray(s[slot]), np.asarray(pool[slot]))


def test_the_kernel_is_chosen_from_platform_mesh_and_shapes():
    assert ss.step_applicable("tpu", None, 128, 64, 128, groups=8)
    assert not ss.step_applicable("cpu", None, 128, 64, 128, groups=8)
    assert not ss.step_applicable("tpu", object(), 128, 64, 128, groups=8)
    assert not ss.step_applicable("tpu", None, 128, 48, 128, groups=8)
    assert ss.step_form(128, 64, 128, 8) == "step_xla"        # a CPU here
    assert ss.state_shape(128, 64, 128, 8) == (64, 128, 128)  # 4 MiB a slot
    assert ss.lane_pack(128, 64, 8) == 2 and ss.lane_pack(32, 128, 1) == 1


def test_kernel_compiles_for_the_chip_in_place_at_the_published_sizes():
    """128 slots of 128 heads of (64, 128) float32 in 8 groups, stored two
    heads a lane row, under donation, compiled for a described v5e: one Mosaic
    call, the pool aliased, no pool-sized temporary."""
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
    n, h, p, ns, g = 128, 128, 64, 128, 8
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # as the chip runs it: 32-bit index arithmetic (the suite turns x64 on)
        with jax.enable_x64(False):
            comp = jax.jit(
                lambda pool, x, dt, A, B, C, D: ss.step(pool, x, dt, A, B, C, D,
                                                        kernel=True),
                donate_argnums=0).lower(
                    sd(n, *ss.state_shape(h, p, ns, g)), sd(n, h, p), sd(n, h),
                    sd(h),
                    sd(n, g, ns), sd(n, g, ns), sd(h)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    text = comp.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%ssm_state_step" in text
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == n * h * p * ns * 4 == 128 * 4 * 2 ** 20
    assert mem.temp_size_in_bytes < 2 ** 20


# --- each layer kind against the reference --------------------------------------

def test_gluon_forward_equals_reference_logits(ref, tiny):
    net = tiny[0]
    seq = np.random.RandomState(0).randint(1, 256, size=40)
    got = net(nd.array(seq[None], dtype="int32")).asnumpy()[0]
    want = _ref_logits(ref, tiny, seq)
    assert got.shape == want.shape == (40, 256)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("unit,kinds", [(0, ("mamba", "experts")),
                                        (1, ("mamba",)),
                                        (2, ("attention", "experts"))])
def test_each_layer_kind_equals_the_references_layer(ref, tiny, unit, kinds):
    """A served layer is a mixer and the expert part behind it, if one
    follows: the reference's one or two layers of those letters."""
    net, weights, cfg = tiny
    at = _letters(net.config)[unit]
    assert tuple(ref.layer_kind(cfg, l) for l in at) == kinds
    p = {n: a for l in at for n, a in weights["layers"][l].items()}
    x = jnp.asarray(np.random.RandomState(unit).randn(1, 21, 64), jnp.float32)
    got, _kept, counts = nh.NemotronHMath(net.config).layer(p, x, None,
                                                            Causal(21))
    with jax.default_matmul_precision("highest"):
        want = x[0]
        for l in at:
            want = ref.layer_forward(want, weights["layers"][l], cfg,
                                     ref.layer_kind(cfg, l))
    assert np.abs(got[0] - want).max() < 2e-5 * np.abs(want).max()
    if "experts" in kinds:
        assert int(counts.sum()) == 21 * 4 and counts.shape == (16,)
    else:
        assert counts is None


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, tiny):
    """Four chips hold four experts each; the held experts' part is summed in
    the latent space and ``W_up`` is linear, so the shares add up; every share
    carries the shared expert, which a deployment counts once."""
    net, weights, cfg = tiny
    w = dict(weights["layers"][1])
    u = jnp.asarray(np.random.RandomState(3).randn(30, 64), jnp.float32)
    key = ref.layer_key(jax.random.PRNGKey(3), 1)
    whole = dict(cfg, experts_held=[0, 16])
    w.update(ref.init_experts(key, whole, jnp.float32, 0, 16))

    def ffn(first, count):
        conf = nh.NemotronHConfig(**dict(
            nh.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
            experts_held=(first, count)))
        p = dict(w, **{n: w[n][first:first + count]
                       for n in ("w_up", "w_down")})
        return nh.NemotronHMath(conf).ffn(p, u)

    uncut, counts = ffn(0, 16)
    shares = [ffn(first, 4)[0] for first in (0, 4, 8, 12)]
    shared = nh._relu2(u, w["shared_up"], w["shared_down"])
    assert np.abs(sum(shares) - 3 * shared - uncut).max() \
        < 1e-5 * np.abs(uncut).max()
    assert np.abs(shares[0] - shared).max() > 1e-3      # a share routes
    # and the uncut layer is the reference's
    with jax.default_matmul_precision("highest"):
        comb, _m = ref.combine_weights(u, w, whole)
        lat = u @ w["latent_down"].T
        want = ref.experts_part(lat, comb, w) @ w["latent_up"].T + shared
    assert np.abs(uncut - want).max() < 2e-5 * np.abs(want).max()
    assert int(counts.sum()) == 30 * 4


# --- through GenerativeServer -------------------------------------------------

def _server(net, **kw):
    cfg = dict(max_batch=2, max_length=64, min_length=8, num_slots=3,
               block_size=4)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


def _programs(eng):
    """The decoder's prefill and step, jitted once an engine (the tests below
    call them directly, not through the lanes)."""
    if not hasattr(eng, "_test_programs"):
        eng._test_programs = (jax.jit(eng._dec._prefill_rows_impl),
                              jax.jit(eng._dec._step_blocks_impl))
    return eng._test_programs


@pytest.fixture(scope="module")
def engine(tiny):
    """One engine for the tests that drive the programs slot by slot: an
    admission writes a slot's arrays whole, so what a test before left in a
    slot is nothing to the next (``test_freed_slot_readmitted...`` holds
    that)."""
    return _server(tiny[0]).engine


def _prefill_and_commit(eng, seq, t0, slot=0, pad=77):
    w = eng._w
    lb = max(8, 1 << (t0 - 1).bit_length())
    ids = np.full((1, lb), pad, np.int32)    # what padding must not leak
    ids[0, :t0] = seq[:t0]
    rows, lg, _c = _programs(eng)[0](w, jnp.asarray(ids), jnp.asarray([t0]))
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    return rows, np.asarray(lg)[0]


def _teacher_forced_logits(eng, seq, t0, slot=0):
    """Prefill ``seq[:t0]`` (the chunked scan in a padded bucket), hand the
    states and K/V over, then decode the rest of ``seq`` token by token
    through the engine's own programs -> (len(seq) - t0 + 1, vocab)."""
    w = eng._w
    out = [_prefill_and_commit(eng, seq, t0, slot)[1]]
    for t in range(t0, len(seq)):
        ids_t = np.zeros(eng.num_slots, np.int32)
        pos = np.zeros(eng.num_slots, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        lg, eng._pool, _c = _programs(eng)[1](
            w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(ids_t),
            jnp.asarray(pos))
        out.append(np.asarray(lg)[slot])
    return np.stack(out)


@pytest.mark.parametrize("t0", [1, 2, 5, 13, 20])
def test_prefill_then_decode_equals_reference_at_every_position(ref, tiny,
                                                                engine, t0):
    """Ragged prompts shorter and longer than a bucket, and shorter than the
    conv's four taps.  Tolerance: float32 on both sides; the chunked scan sums
    in another order than the reference's token-by-token pass and the served
    products run at the CPU's default precision, the reference's at
    ``highest``: 5e-4 of the largest logit, a hundred times below what a lost
    state or a stale ring row moves (the planted tests below)."""
    seq = np.random.RandomState(100 + t0).randint(1, 256, size=29)
    got = _teacher_forced_logits(engine, seq, t0, slot=t0 % 3)
    want = _ref_logits(ref, tiny, seq)[t0 - 1:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 5e-4 * np.abs(want).max()


@pytest.mark.parametrize("t0", [1, 2, 3, 7, 16])
def test_prefill_hands_on_both_arrays_at_the_true_length(tiny, engine, t0):
    """A prompt padded to a bucket: the ring is the convolution's input at
    t0-3 .. t0-1 (row t % 3), zeros where the prompt is shorter; the recurrent
    state is the one the UNPADDED prompt leaves."""
    net, eng = tiny[0], engine
    seq = np.random.RandomState(t0).randint(1, 256, size=t0)
    rows, _lg = _prefill_and_commit(eng, seq, t0)
    ring, state = rows[0]
    conv = net.config.conv_dim
    assert ring.shape == (1, 3, conv) and ring.dtype == jnp.float32
    assert state.shape == (1, 2, 16, 16) and state.dtype == jnp.float32
    w = eng._w
    exact, _lg, _c = eng._dec._prefill_rows_impl(w, jnp.asarray(seq[None]),
                                                 jnp.asarray([t0]))
    assert np.abs(state - exact[0][1]).max() < 1e-5
    p = w["layers"][0]
    di = net.config.d_inner
    mixed = (rms_norm(w["emb"][jnp.asarray(seq)], p["norm"], 1e-5)
             @ p["in_proj"].T)[:, di:di + conv]
    for r in range(3):
        src = [q for q in range(t0 - 3, t0) if q % 3 == r][0]
        want = np.zeros(conv) if src < 0 else np.asarray(mixed[src])
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
        futs = [srv.submit(p, max_new_tokens=24 - len(p)) for p in prompts]
        outs = [f.result(120) for f in futs]
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == 24
        lg = _ref_logits(ref, tiny, o)
        for j in range(24 - len(p)):
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
    fresh = _server(net, num_slots=1).engine
    fresh._test_programs = used._test_programs       # the same programs
    assert np.array_equal(got, _teacher_forced_logits(fresh, second, 2))


def test_a_slot_committed_and_not_yet_adopted_keeps_its_state(engine):
    """A step is not idempotent: a neighbour's tick must not move a committed
    slot's state on before the decode lane owns it."""
    eng = engine
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
    with pytest.raises(mx.MXNetError) as exc:
        serving.GenerativeServer(
            tiny[0], ServerConfig(max_batch=2, max_length=64, min_length=8,
                                  num_slots=2, **kw))
    assert says in str(exc.value)


@pytest.mark.parametrize("pattern", ["EM", "MEE*", "M-E", ""])
def test_a_pattern_without_a_served_reading_is_refused(pattern):
    """A served layer is a mixer and the expert part behind it: a pattern that
    starts on E, has two in a row or a dense letter has no such reading."""
    with pytest.raises(mx.MXNetError):
        nh.NemotronHConfig(**dict(nh.NEMOTRON_H_CONFIGS["nemotron_h_tiny"],
                                  pattern=pattern))


# --- accounting: a state layer with two arrays of two dtypes ----------------------

def test_state_pool_bytes_equal_the_planners_with_a_float32_array_in_it():
    """bfloat16 weights: the ring is priced at 2 bytes, the state at 4; the
    pattern's five letters are three cache-spec layers."""
    from mxnet_tpu.memory import plan_kv_pool

    net = nh.nemotron_h_tiny()
    net.cast("bfloat16")
    net.initialize()
    srv = _server(net, num_slots=3, num_blocks=20)
    eng, spec = srv.engine, srv.engine.cache_spec
    assert net.config.units == (("M", True), ("M", False), ("*", True))
    assert spec.layers == ("state", "state", "kv")
    assert (spec.expert_layers, spec.num_experts) == (2, 16)
    conv = net.config.conv_dim
    assert spec.state_arrays == (((3, conv), None),
                                 ((2, 16, 16), np.dtype("float32")))
    ring, state = eng._pool[0]
    assert (ring.dtype, state.dtype) == (jnp.bfloat16, jnp.float32)
    assert spec.state_array_bytes(2) == (3 * conv * 2, 2 * 16 * 16 * 4)
    per_slot = 2 * (3 * conv * 2 + 2 * 16 * 16 * 4)
    assert spec.state_bytes_per_slot(2) == per_slot
    by_kind = eng.kv_pool_bytes(by_kind=True)
    assert by_kind == {
        "kv_blocks": 2 * 20 * 2 * 4 * 16 * 2, "slot_state": 3 * per_slot,
        "slot_state_arrays": (2 * 3 * 3 * conv * 2, 2 * 3 * 2 * 16 * 16 * 4)}
    assert eng.kv_pool_bytes() == sum(
        by_kind[k] for k in ("kv_blocks", "slot_state")) == plan_kv_pool(
        1, 2, 16, num_blocks=20, block_size=4, dtype="bfloat16",
        state_layers=2, state_arrays=spec.state_arrays, num_slots=3)
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    assert st["cache_bytes"] == by_kind
    assert st["kv_cache"]["state_bytes_per_slot"] == per_slot
    assert (st["kv_layers"], st["state_layers"]) == (1, 2)
    assert st["linear_attention"] == "step_xla"
    assert st["expert_product"] == "every_expert"
    assert st["experts_held"] == (0, 8)


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
        assert rec["kv_tokens"] > 0
        # 8 of 16 experts held in each of 2 layers: the touched among them
        assert 0 < rec["experts_touched_held"] <= min(
            rec["experts_touched"], 2 * 8)
        assert rec["expert_rows_max"] >= rec["expert_rows_mean"] > 0
    for rec, n in zip(batches, (3, 9, 12)):
        assert rec["scan_rows"] == n == rec["n_tokens"]
        assert rec["scan_rows_padded"] == rec["bucket"][0] * rec["bucket"][1]
        assert rec["scan_rows_padded"] > n
    assert ticks[0]["linear_attention"] == "step_xla"
    assert ticks[0]["expert_product"] == "every_expert"
    assert batches[0]["linear_attention"] == "step_xla"
    assert "linear_attention" not in ticks[-1]


def test_expert_product_is_asked_with_the_latent_width(monkeypatch):
    """On a TPU without a mesh at the published sizes: 128 rows a step keep
    every expert on every row (under ``GROUPED_MIN_ROWS``), a prompt bucket of
    384 rows and more goes through the grouped kernel, 22 pairs a row over
    experts of (1,024, 2,688), the LATENT width."""
    from mxnet_tpu.models import moe

    asked = []
    real = moe.grouped_ffn.applicable

    def spy(platform, mesh, rows, k, held, hidden, width, itemsize=2):
        asked.append((rows, k, held, hidden, width))
        return real("tpu", None, rows, k, held, hidden, width, itemsize)

    monkeypatch.setattr(moe.grouped_ffn, "applicable", spy)
    cfg = nh.NemotronHConfig(pattern="MEMEMEMEM*E", experts_held=(0, 128),
                             vocab_size=32768)
    dec = nh.NemotronHDecoder.__new__(nh.NemotronHDecoder)
    dec.cfg = cfg
    assert dec.expert_product(128, "bfloat16") == "every_expert"
    assert dec.expert_product(384, "bfloat16") == "grouped_kernel"
    assert dec.expert_product(512, "bfloat16") == "grouped_kernel"
    assert asked[0] == (128, 22, 128, 1024, 2688)
    assert dec.cache_spec().layers == ("state",) * 5 + ("kv",)
    assert dec.cache_spec().expert_layers == 5


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
    programs = _bench_module("families", "nemotron_h.py").Cell.programs
    for key, low in lowered.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)
    text = lowered["step"].as_text(debug_info=True)
    for scope in ("ssm_state_step", "ssm_project", "latent_project", "moe_ffn",
                  "shared_expert"):
        assert scope in text, scope
    assert "ssm_chunk_scan" in lowered["prefill"].as_text(debug_info=True)


# --- the benchmark's files -------------------------------------------------------

def test_decode_step_bytes_total_to_perf_mds_table():
    fb = _bench_module("flops_bytes", "nemotron_h_decode_step.py")
    cfg = json.load(open(CELL_CONFIG))
    assert fb.layer_counts(cfg) == (5, 1, 5)
    mixer = fb.mixer_params(cfg)
    small = 4096 + 5 * 10240 + 3 * 128 + 8192    # norms, conv, gates' vectors
    assert mixer == 4096 * 18560 + 8192 * 4096 + small
    assert mixer / 1e6 == pytest.approx(109.64, abs=0.01)
    assert (4096 * 18560) / 1e6 == pytest.approx(76.02, abs=0.01)     # in
    assert (8192 * 4096) / 1e6 == pytest.approx(33.55, abs=0.01)      # out
    assert fb.attention_params(cfg) == 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    assert fb.attention_params(cfg) / 1e6 == pytest.approx(35.66, abs=0.01)
    assert fb.expert_params(cfg) == 2 * 1024 * 2688
    assert fb.expert_params(cfg) / 1e6 == pytest.approx(5.505, abs=0.001)
    fixed = fb.expert_layer_fixed_params(cfg)
    assert fixed == 4096 + 512 * 4097 + 2 * 1024 * 4096 + 2 * 4096 * 5376
    assert fixed / 1e6 == pytest.approx(54.53, abs=0.01)
    assert (512 * 4096) / 1e6 == pytest.approx(2.10, abs=0.01)        # router
    assert (2 * 4096 * 5376) / 1e6 == pytest.approx(44.04, abs=0.01)  # shared
    assert fb.expert_layer_params(cfg) / 1e6 == pytest.approx(759.2, abs=0.1)
    total = fb.weight_bytes(cfg)
    assert total / 2 == 5 * mixer + fb.attention_params(cfg) \
        + 5 * fb.expert_layer_params(cfg) + 2 * 32768 * 4096 + 4096
    assert total / 2e6 == pytest.approx(4648.2, abs=0.1)          # parameters
    assert total / 1e9 == pytest.approx(9.30, abs=0.01)
    assert 5 * 128 * fb.expert_bytes(cfg) / 1e9 == pytest.approx(7.05, abs=0.01)
    assert fb.kv_bytes_per_token(cfg) == 1024                     # 1 KiB
    assert fb.recurrent_bytes_per_slot(cfg) == 4 * 2 ** 20        # 4 MiB
    assert fb.state_bytes_per_slot(cfg) == 5 * (4 * 2 ** 20 + 60 * 1024)
    assert fb.state_bytes_per_slot(cfg) / 2 ** 20 == pytest.approx(20.3, abs=0.05)
    assert 128 * fb.state_bytes_per_slot(cfg) / 1e9 == pytest.approx(2.72, abs=0.01)
    # a tick at 128 full slots: every held expert touched
    state = 2 * 128 * fb.state_bytes_per_slot(cfg)
    need = fb.bytes_needed(cfg, active_slots=128, kv_tokens=128 * 900,
                           experts_touched=5 * 128)
    assert need == (fb.fixed_weight_bytes(cfg) + 640 * fb.expert_bytes(cfg)
                    + 128 * 4096 * 2 + (128 * 900 + 128) * 1024 + state)
    assert fb.bytes_needed(cfg, 128, 128 * 900, 640, state_bytes=state) == need
    assert fb.bytes_needed(cfg, 128, 128 * 900, 5 * 512) == need   # cut to held
    assert state / 1e9 == pytest.approx(5.45, abs=0.01)
    assert need / 1e9 == pytest.approx(14.6, abs=0.05)
    assert 100 * state / need == pytest.approx(37, abs=1)
    assert 100 * 640 * fb.expert_bytes(cfg) / need == pytest.approx(48, abs=1)
    assert need / 819e9 * 1e3 == pytest.approx(17.8, abs=0.1)     # ms a tick
    assert 128 / (need / 819e9) == pytest.approx(7200, abs=50)    # tokens/s
    # operations: 5.5 routed experts a row lie here; far below the bytes' time
    flops = fb.flops_needed(cfg, 128, 128 * 900)
    assert flops / 197e12 < 0.1 * need / 819e9
    per_row = 5 * mixer + fb.attention_params(cfg) \
        + 5 * (fixed + 5.5 * 2 * 1024 * 2688) + 32768 * 4096 \
        + 5 * 2 * 128 * 64 * 128
    assert flops == 2 * (128 * per_row + 2 * 32 * 128 * 128 * 900)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(CELL_CONFIG))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == \
                    "NVIDIA-Nemotron-3-Super-120B-A12B-BF16":
                row = json.loads(line)
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "hybrid_override_pattern",
                       "n_routed_experts", "vocab_size"}
    assert changed == set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["source"] == row["source_url"]
    # the cut is one whole period of the published pattern, letters 27-37
    assert cfg["hybrid_override_pattern"] == "MEMEMEMEM*E" \
        == row["config"]["hybrid_override_pattern"][27:38]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"] == 11
    assert [row["config"]["hybrid_override_pattern"].count(c)
            for c in "ME*"] == [40, 40, 8]
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]) \
        == (4096, 128, 64, 128, 8, 4)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (32, 2, 128)
    assert (cfg["router_experts"], cfg["num_experts_per_tok"],
            cfg["moe_latent_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["routed_scaling_factor"]) == (512, 22, 1024, 2688, 5376, 5)
    assert "4 chips that share each layer" in cfg["deployment"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "nemotron3_super_120b_l11_ep4"][0]
    assert set(entry["reduced"]) == changed
    assert entry["source"] == row["source_url"]
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 128]
    assert cfg["vocab_size"] * 4 == 131072
    cell = [w for w in bench["workloads"]
            if w["name"] == "nemotron3_super.chat_decode_sat"][0]
    assert (cell["config"], cell["chips"]) == ("nemotron3_super_120b_l11_ep4", 1)
    # by name, not by place: a later PR appends its own cell behind it
    assert bench["workloads"].index(cell) == 9
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json")))
    assert (mix["clients"], mix["system"]["num_slots"]) == (256, 128)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 32, "hi": 512}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert mix["system"]["max_length"] == 1536 == mix["check"]["pad_tokens"]
    assert (mix["system"]["max_batch"], mix["system"]["block_size"],
            mix["distinct_sizes"]) == (1, 16, 256)
    for name in ("hybrid_step_roofline", "ssm_state_step_roofline",
                 "ssm_state_bytes_share"):
        m = [m for m in bench["per_layer"] if m["name"] == name][0]
        assert m["workloads"] == [cell["name"]]


def test_the_program_is_built_at_the_published_widths():
    """The family's constructor call, with the cell's configuration: shapes
    only (nothing is allocated)."""
    cfg = json.load(open(CELL_CONFIG))
    conf = nh.NemotronHConfig(
        pattern=cfg["hybrid_override_pattern"],
        num_experts=cfg["router_experts"],
        experts_held=tuple(cfg["experts_held"]), vocab_size=cfg["vocab_size"])
    assert conf.units == (("M", True),) * 4 + (("M", False), ("*", True))
    shapes = [nh._layer_param_shapes(conf, u) for u in conf.units]
    fb = _bench_module("flops_bytes", "nemotron_h_decode_step.py")
    got = sum(int(np.prod(s)) for layer in shapes for s in layer.values())
    assert got == 5 * fb.mixer_params(cfg) + fb.attention_params(cfg) \
        + 5 * fb.expert_layer_params(cfg)
    # two heads of 64 a lane row: 4 MiB a slot a layer, as (128, 64, 128)
    assert conf.state_arrays() == (((3, 10240), None),
                                   ((64, 128, 128), "float32"))
    assert (conf.d_inner, conf.conv_dim) == (8192, 10240)
    # the whole published pattern reads as 48 served layers
    whole = nh.NemotronHConfig()
    assert len(whole.pattern) == 88 and whole.num_layers == 48
    assert whole.num_expert_layers == 40


# --- the cell, rehearsed, and what its check refuses ------------------------------

@pytest.fixture
def harness(monkeypatch, tmp_path):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as harness

    # a traced run of its own trace directory: the checkout's one
    # ``.chipbench_trace`` is shared by every test process
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
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_nemotron_h``:
    the new family, reference, traffic keys and readers at a tiny size, the
    second quarter of the experts held (8 of 16 from the 5th).  What is held
    here does not depend on how many ticks the machine's load lets into the
    window: the readers' values are ratios of counters."""
    res = harness.run(["--workload", "tiny_nemotron_h.closed", "--seed",
                       "4000000007", "--seconds", "1", "--trace", str(trace),
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
        assert not {"hybrid_step_roofline", "ssm_state_step_roofline",
                    "decode_step_ms"} & set(res["metrics"])
        share = res["metrics"].get("ssm_state_bytes_share")
        assert share is None or 0 < share["value"] < 100
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def _run_planted(harness, capsys):
    res = harness.run(["--workload", "tiny_nemotron_h.closed", "--seed", "11",
                       "--seconds", "1", "--trace", "0", "--control", "0"],
                      require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "FAILED" in out
    return _compared(out)


def test_a_state_not_handed_over_is_not_correct(harness, capsys, monkeypatch):
    """Planted: the prefill's recurrent state does not reach the slot, which
    decodes from zeros (the ring arrives)."""
    whole = nh.NemotronHDecoder._sequence_state

    def lost(self, kept, t0):
        ring, state = whole(self, kept, t0)
        return ring, jnp.zeros_like(state)

    monkeypatch.setattr(nh.NemotronHDecoder, "_sequence_state", lost)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > 10 * _limits()["gap_mean_limit"]


def test_a_padded_row_that_moves_the_state_is_not_correct(harness, capsys,
                                                          monkeypatch):
    """Planted: the scan lets the padded end of a bucket into the state."""
    whole = ss.chunk_scan
    monkeypatch.setattr(ss, "chunk_scan",
                        lambda *a, live=None, **kw: whole(*a, **kw))
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]


def test_the_latent_up_projection_left_out_is_not_correct(harness, capsys,
                                                          monkeypatch):
    """Planted: the experts' latent sum is added back without ``W_up`` (the
    tiny latent width is not the model's, so the rows are tiled to fit: any
    fixed map that is not the projection)."""
    real = nh.NemotronHMath.ffn

    def ffn(self, p, u, live=None):
        lat, h = p["latent_up"].shape[1], p["latent_up"].shape[0]
        eye = jnp.tile(jnp.eye(lat, dtype=p["latent_up"].dtype),
                       (h // lat, 1))
        return real(self, dict(p, latent_up=eye), u, live)

    monkeypatch.setattr(nh.NemotronHMath, "ffn", ffn)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]


def test_a_conv_ring_one_row_stale_is_not_correct(harness, capsys, monkeypatch):
    """Planted: the ring handed over is the one of a prompt a token shorter."""
    whole = nh.NemotronHDecoder._sequence_state

    def stale(self, kept, t0):
        ring, _state = whole(self, kept, jnp.maximum(t0 - 1, 0))
        return ring, whole(self, kept, t0)[1]

    monkeypatch.setattr(nh.NemotronHDecoder, "_sequence_state", stale)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _limits()["gap_mean_limit"]

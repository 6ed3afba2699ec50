"""GLM-MoE-DSA in the server: latent attention over the keys a learned
indexer selects, a shared expert beside the held share of the routed
ones, through the shared paged programs and a latent cache kind.

The reference is ``chipbench/references/glm_moe_dsa.py`` (plain
float32, expanded attention, no cache, nothing of the program
imported); a tiny preset whose ``index_topk`` (8) is smaller than the
tests' sequences, so that the selection is real at every size here.
"""
from __future__ import annotations

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
from mxnet_tpu.models import glm_moe_dsa as glm
from mxnet_tpu.ops import latent_cache, sparse_select
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.serving.protocol import Request
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
TOPK = 8


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_glm_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "glm_moe_dsa.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_attention_heads": cfg.num_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "index_n_heads": cfg.index_n_heads,
            "index_head_dim": cfg.index_head_dim,
            "index_topk": cfg.index_topk,
            "router_experts": cfg.num_experts,
            "experts_held": list(cfg.experts_held),
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_shared_experts": cfg.n_shared_experts,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.norm_eps,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "vocab_size": cfg.vocab_size,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing, selection and attention are far from uniform)
    -> (net, the reference's weight tree, the reference's config)."""
    net = glm.glm_moe_dsa_tiny(**overrides)
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
    net.lm_head.weight.set_data(nd.NDArray(top["head"]))
    return net, {"top": top, "layers": layers}, cfg


@pytest.fixture(scope="module")
def tiny(ref):
    return _net_and_weights(ref)


def _server(net, **kw):
    cfg = dict(max_batch=2, max_length=64, min_length=8, num_slots=3,
               block_size=4)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


def _close(got, want, tol=5e-4):
    """float32 against float32 in another order of operations (the
    absorbed form, tiles, a cache): rounding alone."""
    assert got.shape == want.shape
    assert np.abs(got - want).max() < tol * np.abs(want).max()


# --- the mathematics, once -------------------------------------------------------

def test_gluon_forward_in_the_plain_form_equals_reference(ref, tiny):
    net, weights, cfg = tiny
    ids = np.random.RandomState(0).randint(1, 256, size=(2, 40))
    got = net(nd.array(ids, dtype="int32")).asnumpy()
    for b in range(2):
        _close(got[b], np.asarray(ref.forward(cfg, weights, ids[b])))


def test_one_layer_equals_the_references_layer(ref, tiny):
    """An expert layer alone, so that a fault in it is not averaged away
    by the layers behind it."""
    net, weights, cfg = tiny
    x = np.random.RandomState(1).randn(1, 16, 64).astype(np.float32)
    got = net.layers[1](nd.array(x)).asnumpy()[0]
    with jax.default_matmul_precision("highest"):
        want, _bits, _m = ref.layer_forward(jnp.asarray(x[0]),
                                            weights["layers"][1], cfg,
                                            "experts")
    _close(got, np.asarray(want))


def test_the_references_blocks_and_key_extents_change_nothing(ref, tiny, a_sequence,
                                                              monkeypatch):
    """The reference at the check's sizes runs in blocks of query rows whose
    groups read the keys up to their own end: at blocks of 8 rows in groups
    of 2 (three extents over 40 tokens) it says what it says in one block.
    (The float8 control rounds with one scale a tensor, so ITS readings move
    with the blocks; it is a control, not a result.)"""
    _net, weights, cfg = tiny
    seq, want, chosen = a_sequence
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    monkeypatch.setattr(ref, "KEY_GROUP", 2)
    got, picked = jax.jit(lambda w, ids: ref._forward(
        cfg, w, ids, False, "indexer", True))(weights, jnp.asarray(seq))
    assert (np.asarray(picked) == np.asarray(chosen)).all()
    _close(np.asarray(got), np.asarray(want), 1e-5)


def _teacher_forced(eng, seq, t0, slot=0):
    """Prefill ``seq[:t0]`` then decode the rest of ``seq`` token by token
    through the engine's own programs -> (the logits of every position
    from the prefill's last row on, (len(seq) - t0 + 1, vocab); what each
    layer of each step selected, a list of (layers, k))."""
    dec, w = eng._dec, eng._w
    lb = max(8, 1 << (t0 - 1).bit_length())
    ids = np.zeros((1, lb), np.int32)
    ids[0, :t0] = seq[:t0]
    rows, lg, _c = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                          jnp.asarray([t0]))
    out, picked = [np.asarray(lg)[0]], []
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    for t in range(t0, len(seq)):
        ids_t = np.zeros(eng.num_slots, np.int32)
        pos = np.zeros(eng.num_slots, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        lg, eng._pool, _c, sel = dec._step_blocks_impl(
            w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(ids_t),
            jnp.asarray(pos))
        out.append(np.asarray(lg)[slot])
        picked.append(np.asarray(sel)[:, slot])
    return np.stack(out), picked


@pytest.fixture(scope="module")
def a_sequence(ref, tiny):
    """40 tokens, the reference's logits at every position and what each
    of its layers selects there (the model is causal: a prefix's are the
    whole sequence's)."""
    _net, weights, cfg = tiny
    seq = np.random.RandomState(100).randint(1, 256, size=40)
    want, chosen = ref.forward(cfg, weights, seq, with_selection=True)
    return seq, np.asarray(want), np.asarray(chosen)


@pytest.mark.parametrize("t0", [1, 5, 8, 13, 20, 31])
def test_prefill_then_decode_through_the_latent_cache_equals_reference(
        tiny, a_sequence, t0):
    """Prompts shorter and longer than ``index_topk`` and than a bucket:
    the prefill in the absorbed form over its selected rows, the
    hand-over into the latent and index-key pools, then every decode step
    selecting from the cache, against the reference's cache-less expanded
    forward; and every step's selected set is the reference's own."""
    net = tiny[0]
    eng = _server(net).engine
    seq, want, chosen = a_sequence
    got, picked = _teacher_forced(eng, seq[:t0 + 9], t0)
    _close(got, want[t0 - 1:t0 + 9])
    for j, sel in enumerate(picked):
        t = t0 + j
        for l in range(len(sel)):
            mine = set(sel[l][sel[l] >= 0].tolist())
            assert len(mine) == min(t + 1, TOPK)
            assert mine == set(np.flatnonzero(chosen[l, t]).tolist()), (t, l)


def test_selection_is_exact_with_ties_to_the_earlier_position():
    scores = jnp.asarray([[3., 1., 3., 2., 3., 0., 3., 9.]])
    visible = jnp.asarray([[True] * 7 + [False]])
    idx, valid = sparse_select.select(scores, visible, 3)
    assert idx.tolist() == [[0, 2, 4]] and valid.all()
    idx, valid = sparse_select.select(scores[:, :2], visible[:, :2], 3)
    assert idx.shape == (1, 2) and valid.all()
    idx, valid = sparse_select.select(scores, jnp.arange(8)[None] < 2, 3)
    assert sorted(idx[0][np.asarray(valid[0])].tolist()) == [0, 1]
    assert int(valid.sum()) == 2


def test_the_selection_as_a_mask_is_the_sorted_selection_to_the_bit():
    """``select_mask`` (two bisections, no sort) against ``select``
    (``lax.top_k``): many equal scores, signed zeros, rows that see fewer
    positions than they may read."""
    rs = np.random.RandomState(9)
    t, k = 300, 37
    for trial in range(4):
        sc = rs.randn(5, t).astype(np.float32)
        if trial % 2:
            sc = np.round(sc * 2) / 2
        sc[0, :50], sc[1, 10:40] = -0.0, 0.0
        vis = np.arange(t)[None, :] <= rs.randint(0, t, size=(5, 1))
        idx, valid = sparse_select.select(jnp.asarray(sc), jnp.asarray(vis), k)
        got = sparse_select.select_mask(jnp.asarray(sc), jnp.asarray(vis), k)
        assert (np.asarray(got) == np.asarray(
            sparse_select.chosen_mask(idx, valid, t))).all()
        assert (np.asarray(got).sum(-1) == np.minimum(vis.sum(-1), k)).all()
        # more may be read than there are positions: all that are visible
        assert (np.asarray(sparse_select.select_mask(
            jnp.asarray(sc), jnp.asarray(vis), t + 100)) == vis).all()


@pytest.mark.parametrize("extent", [128, 4096])
@pytest.mark.parametrize("kind", ["latent", "kv"])
def test_tiled_prefill_equals_the_plain_form(monkeypatch, kind, extent):
    """The prefill's tiles (``ops.sparse_select.causal_tiles``: one loop,
    one scoring, one mask for both kinds that select) against the kind's
    plain form, at sizes where the prefill runs in several query tiles, in
    one group of keys or three (a group reads the keys up to its own end),
    scores nothing where a tile sees no more than ``topk`` positions, and
    skips the tiles past the sequences' ends: the latent kind's absorbed
    form against the expanded one, the K/V kind's masked form against
    ``masked_attention`` under the sorted selection."""
    monkeypatch.setattr(sparse_select, "KEY_EXTENT", extent)
    rs = np.random.RandomState(2)
    b, t, nh, dn, dr, dv, rank, ih, idim = 2, 384, 2, 8, 4, 8, 16, 2, 8
    topk = 40 if kind == "latent" else 160
    keys = jnp.asarray(rs.randn(b, t, idim), jnp.float32)
    q_idx = jnp.asarray(rs.randn(b, t, ih, idim), jnp.float32)
    w_idx = jnp.asarray(rs.randn(b, t, ih), jnp.float32)
    lengths = jnp.asarray([200, 130])
    with jax.default_matmul_precision("highest"):
        if kind == "latent":
            latent = jnp.asarray(rs.randn(b, t, rank + dr), jnp.float32)
            parts = (jnp.asarray(rs.randn(b, t, nh, dn), jnp.float32),
                     jnp.asarray(rs.randn(b, t, nh, dr), jnp.float32),
                     q_idx, w_idx)
            args = (jnp.asarray(rs.randn(nh, dn, rank), jnp.float32),
                    jnp.asarray(rs.randn(nh, dv, rank), jnp.float32), 0.3,
                    lambda heads: heads.reshape(heads.shape[:2] + (-1,)))
            plain = latent_cache.plain_causal_attention(
                lambda *p: p, latent, keys, parts, topk, *args)
            tiled = latent_cache.causal_attention(
                lambda *p: p, latent, keys, parts, lengths, topk, *args)
        else:
            q = jnp.asarray(rs.randn(b, 2 * nh, t, dn), jnp.float32)
            k = jnp.asarray(rs.randn(b, nh, t, dn), jnp.float32)
            v = jnp.asarray(rs.randn(b, nh, t, dn), jnp.float32)
            plain = sparse_select.kv_plain_causal_attention(
                q, k, v, q_idx, w_idx, keys, topk)
            tiled = sparse_select.kv_causal_attention(
                q, k, v, q_idx, w_idx, keys, lengths, topk)
    plain = np.asarray(plain).reshape(b, t, -1)
    tiled = np.asarray(tiled).reshape(b, t, -1)
    # rows of the tiles that hold a live row of some sequence
    live = -(-200 // sparse_select.QUERY_TILE) * sparse_select.QUERY_TILE
    _close(tiled[:, :live], plain[:, :live], 1e-5)
    assert not tiled[:, live:].any()


# --- the held share of the experts ----------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(ref, tiny):
    """16 chips, one expert each: the routed parts of the 16 shares and
    the shared expert, counted once, are the uncut layer's feed-forward."""
    net, weights, cfg = tiny
    w = weights["layers"][1]
    u = jnp.asarray(np.random.RandomState(3).randn(24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut_cfg = glm.GlmMoeDsaConfig(**glm.GLM_CONFIGS["glm_moe_dsa_tiny"])
        whole, counts = glm.GlmMath(uncut_cfg).ffn(w, u)
        shared = glm._swiglu(u, w["shared_gate"], w["shared_up"],
                             w["shared_down"])
        total = shared
        for e in range(16):
            part_cfg = glm.GlmMoeDsaConfig(
                **glm.GLM_CONFIGS["glm_moe_dsa_tiny"], experts_held=(e, 1))
            bank = {n: w[n][e:e + 1] for n in ("w_gate", "w_up", "w_down")}
            y, c = glm.GlmMath(part_cfg).ffn({**w, **bank}, u)
            assert (np.asarray(c) == np.asarray(counts)).all()
            total = total + (y - shared)
    _close(np.asarray(total), np.asarray(whole), 1e-5)
    assert int(np.asarray(counts).sum()) == 24 * 4


def test_a_held_share_serves_as_the_references_share(ref):
    """A server that holds experts 4..11 of 16 against the reference given
    the same ``experts_held``."""
    net, weights, cfg = _net_and_weights(ref, seed=5, experts_held=(4, 8))
    assert cfg["experts_held"] == [4, 8]
    assert net.layers[1].w_gate.shape == (8, 64, 32)
    ids = np.random.RandomState(4).randint(1, 256, size=40)
    want = np.asarray(ref.forward(cfg, weights, ids))
    _close(net(nd.array(ids[None], dtype="int32")).asnumpy()[0], want)
    eng = _server(net).engine
    assert eng.experts_held == (4, 8)
    got, _ = _teacher_forced(eng, ids[:20], 11)
    _close(got, want[10:20])


# --- through the lanes ------------------------------------------------------------

def _generate(srv, prompts, max_new):
    reqs = [Request(prompt_ids=np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    for r in reqs:
        srv._submit(r)
    return reqs, [r.future.result(120) for r in reqs]


def _greedy(ref, cfg, weights, prompt, n, length=40):
    """The reference's own greedy loop, every pass at one length (the
    model is causal: what lies behind a position does not reach it)."""
    seq = np.zeros(length, np.int64)
    seq[:len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        seq[at] = int(np.asarray(ref.forward(cfg, weights, seq))[at - 1].argmax())
    return seq[:len(prompt) + n].tolist()


def test_served_tokens_and_last_selection_follow_the_reference(ref, tiny):
    net, weights, cfg = tiny
    rs = np.random.RandomState(6)
    prompts = [rs.randint(1, 256, size=n) for n in (3, 9, 17, 26)]
    with _server(net) as srv:
        reqs, outs = _generate(srv, prompts, [6, 5, 7, 4])
    for req, p, out, n in zip(reqs, prompts, outs, [6, 5, 7, 4]):
        assert out.tolist() == _greedy(ref, cfg, weights, p, n)
        # what the last step read: its query is the last token but one
        pos, sel = req.selected
        assert pos == len(out) - 2 and sel.shape == (3, TOPK)
        whole = np.zeros(40, np.int64)
        whole[:len(out)] = out
        _lg, chosen = ref.forward(cfg, weights, whole, with_selection=True)
        for l in range(3):
            assert set(sel[l][sel[l] >= 0].tolist()) \
                == set(np.flatnonzero(np.asarray(chosen)[l, pos]).tolist())


def test_freed_slot_readmitted_gives_a_fresh_servers_answer(tiny):
    net = tiny[0]
    rs = np.random.RandomState(7)
    a, b = rs.randint(1, 256, size=21), rs.randint(1, 256, size=10)
    with _server(net, num_slots=1) as srv:
        srv.generate(a, 5)
        again = srv.generate(b, 6)
    with _server(net, num_slots=1) as srv:
        fresh = srv.generate(b, 6)
    assert again.tolist() == fresh.tolist()


# --- refusals, accounting, the lane log -------------------------------------------

def _draft():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


@pytest.mark.parametrize("name,kw,says", [
    ("radix", dict(radix_cache=True), "latent blocks"),
    ("speculation", dict(draft_net="draft", spec_k=2), "one new token"),
    ("int8", dict(int8=True), "int8=True"),
    ("mesh", dict(), "latent pool"),
])
def test_options_refused_for_a_latent_engine(tiny, name, kw, says):
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


def test_cache_bytes_by_kind_match_the_planner_and_count_the_padding(tiny):
    from mxnet_tpu.memory import plan_kv_pool

    net = tiny[0]
    srv = _server(net, num_slots=3, num_blocks=20)
    eng = srv.engine
    spec = eng.cache_spec
    assert spec.layers == ("latent",) * 3
    assert (spec.kv_layers, spec.state_layers, spec.latent_layers) == (0, 0, 3)
    assert (spec.latent_dim, spec.index_dim, spec.select_topk) == (40, 16, 8)
    # a latent row of 40 and an index key of 16 are stored 128 lanes wide
    assert latent_cache.stored_width(40) == 128
    assert latent_cache.stored_width(576) == 640
    assert eng._pool[0][0].shape == (20, 1, 4, 128)
    by_kind = eng.kv_pool_bytes(by_kind=True)
    assert by_kind == {"kv_blocks": 0, "slot_state": 0,
                       "latent_blocks": 3 * 20 * 4 * 128 * 4,
                       "index_key_blocks": 3 * 20 * 4 * 128 * 4}
    assert eng.kv_pool_bytes() == sum(by_kind.values()) == plan_kv_pool(
        0, 0, 8, num_blocks=20, block_size=4, latent_layers=3, latent_dim=40,
        index_dim=16)
    # at the published widths: 640 + 128 lanes a token a layer in bfloat16
    assert latent_cache.bytes_per_block(16, 576, 128, 2) == 16 * 768 * 2
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    assert st["cache_bytes"] == by_kind
    assert (st["kv_layers"], st["latent_layers"]) == (0, 3)
    assert st["experts_held"] == (0, 16)
    assert st["decode_attention"] == st["prefill_attention"] == "latent_sparse"
    mgr = srv.replicas[0].mgr
    assert mgr.kv_bytes_per_block == 3 * 4 * (128 + 128) * 4
    assert mgr.kv_bytes_per_block * 20 == eng.kv_pool_bytes()


def test_lane_log_carries_what_was_visible_and_what_was_read(tiny):
    net = tiny[0]
    since = time.perf_counter()
    prompt = np.arange(1, 14)
    with _server(net, num_slots=1) as srv:
        srv.generate(prompt, max_new_tokens=6)
    pre = tracing.lane_log("prefill.batch", since=since)
    ticks = tracing.lane_log("decode.tick", since=since)
    assert pre[0]["prefill_attention"] == "latent_sparse"
    assert pre[0]["latent_layers"] == 3
    # 13 rows: 1 + 2 + .. + 13 visible, at most 8 read a row
    assert pre[0]["kv_visible"] == 13 * 14 // 2
    assert pre[0]["kv_selected"] == 8 * 9 // 2 + 5 * 8
    assert ticks[0]["decode_attention"] == "latent_sparse"
    assert [t["kv_visible"] for t in ticks] == [14, 15, 16, 17, 18]
    assert all(t["kv_selected"] == 8 for t in ticks)
    assert all(t["kv_visible"] == t["kv_tokens"] for t in ticks)
    assert ticks[0]["experts_touched"] > 0


def test_a_llama_server_has_no_selection_fields():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    since = time.perf_counter()
    with _server(net) as srv:
        req, = _generate(srv, [np.arange(1, 9)], [3])[0]
        st = srv.stats()
    assert req.selected is None and st["latent_layers"] == 0
    assert set(st["cache_bytes"]) == {"kv_blocks", "slot_state"}
    for rec in tracing.lane_log("decode.tick", since=since) \
            + tracing.lane_log("prefill.batch", since=since):
        assert "kv_visible" not in rec and "latent_layers" not in rec


def test_a_long_every_expert_call_goes_in_row_chunks_to_the_same_result():
    """Past ``EVERY_EXPERT_ROWS`` rows the product runs in chunks: no
    ``(rows, held, width)`` array of the whole call."""
    from mxnet_tpu.models import moe

    rs = np.random.RandomState(8)
    n, h, i, e, held = 2 * moe.EVERY_EXPERT_ROWS, 16, 8, 8, 4
    x = jnp.asarray(rs.randn(n, h), jnp.float32)
    router = jnp.asarray(rs.randn(e, h), jnp.float32)
    bank = [jnp.asarray(rs.randn(*s), jnp.float32) * 0.3
            for s in ((held, h, i), (held, h, i), (held, i, h))]
    kw = dict(score="sigmoid", scale=2.5, experts_held=(2, held))
    whole, counts = moe.routed_ffn(x, router, *bank, 2, **kw)
    parts = [moe.routed_ffn(x[a:a + 1024], router, *bank, 2, **kw)
             for a in range(0, n, 1024)]
    _close(np.asarray(whole), np.concatenate([np.asarray(p[0]) for p in parts]),
           1e-6)
    assert (np.asarray(counts) == sum(np.asarray(p[1]) for p in parts)).all()
    text = jax.jit(lambda x: moe.routed_ffn(x, router, *bank, 2, **kw)[0]) \
        .lower(x).as_text()
    assert f"tensor<{moe.EVERY_EXPERT_ROWS}x{held}x{i}xf32>" in text
    assert f"tensor<{n}x{held}x{i}xf32>" not in text
    # a chunk without a live row (a bucket's padded end) is not computed
    live = jnp.arange(n) < moe.EVERY_EXPERT_ROWS - 5
    y, c = moe.routed_ffn(x, router, *bank, 2, live=live, **kw)
    y = np.asarray(y)
    _close(y[:moe.EVERY_EXPERT_ROWS], np.asarray(whole)[:moe.EVERY_EXPERT_ROWS],
           1e-6)
    assert not y[moe.EVERY_EXPERT_ROWS:].any()
    assert int(np.asarray(c).sum()) == 2 * (moe.EVERY_EXPERT_ROWS - 5)


def test_compiled_program_names_are_the_benchmarks(tiny):
    fam = _bench_module("families", "glm_moe_dsa.py")
    net = tiny[0]
    eng = _server(net).engine
    assert fam.PROGRAMS["step"] == r"^jit__step_fn"
    assert eng._step.__wrapped__.__name__ == "_step_fn"
    assert eng._prefill.__wrapped__.__name__ == "_prefill_fn"
    # the four facts a profile reads apart are names in the programs
    dec, w = eng._dec, eng._w
    text = jax.jit(lambda w, ids, t0: dec._prefill_rows_impl(w, ids, t0)) \
        .lower(w, jnp.zeros((1, 16), jnp.int32), jnp.asarray([16])) \
        .as_text(debug_info=True)
    for scope in ("dsa_scoring", "dsa_selection", "mla_selected_attention",
                  "mla_project", "moe_ffn", "shared_expert"):
        assert scope in text, scope


# --- the benchmark's files ----------------------------------------------------------

def test_parameter_and_byte_tables_total_to_the_issues():
    pre = _bench_module("flops_bytes", "glm_moe_dsa_prefill.py")
    dec = _bench_module("flops_bytes", "glm_moe_dsa_decode_step.py")
    cfg = json.load(open(os.path.join(BENCH, "configs", "glm5_l5_ep16.json")))
    mla, idx, expert, router, dense = pre.linear_params(cfg)
    assert mla == 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 \
        + 512 * 64 * 448 + 16384 * 6144 == 165_019_648
    assert idx == 2048 * 4096 + 6144 * 128 + 6144 * 32 == 9_371_648
    assert expert == 3 * 6144 * 2048 == 37_748_736
    assert (router, dense) == (6144 * 256, 3 * 6144 * 12288)
    assert pre.held_experts_per_token(cfg) == 0.5
    # 2.66 GFLOP a token of products
    assert 2 * pre.params_per_token(cfg) / 1e9 == pytest.approx(2.662, abs=1e-3)
    d, m = dec.layer_params(cfg)
    assert d / 1e6 == pytest.approx(400.9, abs=0.05)
    assert m / 1e6 == pytest.approx(817.7, abs=0.05)
    assert dec.weight_bytes(cfg) / 1e9 == pytest.approx(7.82, abs=0.005)
    assert dec.expert_bytes(cfg) == 75_497_472
    assert dec.cache_bytes_per_token(cfg) == 7040
    # the model the cell builds has exactly these parameters
    net_cfg = glm.GlmMoeDsaConfig(num_layers=5, first_k_dense=1,
                                  experts_held=(0, 16), vocab_size=19360)
    n = sum(int(np.prod(s)) for l in range(5)
            for s in glm._layer_param_shapes(net_cfg, l).values()) \
        + 2 * 19360 * 6144 + 6144
    assert 2 * n == dec.weight_bytes(cfg)
    # the pool as stored: 640 + 128 lanes a token a layer
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "longdoc_prefill.json")))["system"]
    assert 5 * mix["num_blocks"] * latent_cache.bytes_per_block(
        mix["block_size"], 576, 128, 2) == 393_216 * 7680
    # operations: a 16k prompt's products, scores and attention
    n16 = 16384
    assert pre.selected_pairs(n16, 2048) == 2048 * 2049 // 2 + (n16 - 2048) * 2048
    f = pre.flops_needed(cfg, n16)
    assert f == 2 * pre.params_per_token(cfg) * n16 \
        + 5 * (8192 * (n16 * (n16 + 1) // 2)
               + 2 * 64 * 512 * pre.selected_pairs(n16, 2048)) \
        + 2 * 19360 * 6144
    assert 5 * 8192 * n16 * n16 / 2 / 1e12 == pytest.approx(5.5, abs=0.05)
    # a tick: fixed weights, the touched experts, index keys to each
    # position, the selected latent rows, the new rows written
    need = dec.bytes_needed(cfg, active_slots=12, kv_visible=200_000,
                            kv_selected=12 * 2048, experts_touched=50)
    assert need == dec.fixed_weight_bytes(cfg) + 50 * dec.expert_bytes(cfg) \
        + 12 * 6144 * 2 + 5 * 2 * (200_000 * 128 + 12 * 2048 * 576) \
        + 12 * 7040
    assert need < dec.weight_bytes(cfg)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(os.path.join(BENCH, "configs", "glm5_l5_ep16.json")))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == "GLM-5":
                row = json.loads(line)
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"}
    assert changed == set(cfg["reduced"]) == set(cfg["published"])
    assert all(cfg["published"][k] == row["config"][k] for k in changed)
    assert cfg["source"] == row["source_url"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "glm5_l5_ep16"][0]
    assert set(entry["reduced"]) == changed and entry["source"] == cfg["source"]
    assert cfg["router_experts"] == 256 and cfg["experts_held"] == [0, 16]
    cell = [w for w in bench["workloads"]
            if w["name"] == "glm5.longdoc_prefill"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("glm5_l5_ep16", "longdoc_prefill", 1)
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in bench[g] if cell["name"] in m.get("workloads", ())}
    assert {"ttft_p90_ms", "dsa_selected_share", "dsa_prefill_roofline",
            "mla_step_roofline", "prefill_ms_per_ktok", "queue_wait_p90_ms",
            "gen_late_p90_ms", "prefill_busy_share", "prefill_gated_share",
            "device_idle_share.prefill"} <= reports
    assert not reports & {"experts_touched_share", "expert_rows_max_over_mean",
                          "prefill_roofline"}
    mix = json.load(open(os.path.join(BENCH, "traffic", "longdoc_prefill.json")))
    assert mix["driver"] == "open_loop_schedule" and mix["order_seed"] == 23
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 8192,
                                    "hi": 28672}
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"], rel=0.03)
    assert mix["system"]["max_length"] == 32768
    assert mix["system"]["num_blocks"] * mix["system"]["block_size"] == 393_216


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
    rows = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            rows[name] = float(rest.split(" limit ")[0])
    return rows


DATA = os.path.join(BENCH, "tests", "data_glm")
LIMIT_ROWS = (("served_logit_gap_mean", "gap_mean_limit"),
              ("served_logit_gap_max_steady", "gap_steady_limit"),
              ("served_logit_gap_share_over_0.05", "gap_share_limit"),
              ("selection_miss_max", "selection_miss_limit"))


def _check_limits():
    return json.load(open(os.path.join(DATA, "traffic", "open.json")))["check"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_glm``: the
    new family, reference, traffic keys and readers at a tiny size, a share
    of the experts held (8 of 16 from the 5th) and ``index_topk`` 8 under
    prompts of 8 to 40."""
    res = harness.run(["--workload", "tiny_glm.open", "--seed", "4000000007",
                       "--seconds", "3", "--trace", str(trace),
                       "--control", "1"],
                      require_tpu=False, data_dir=DATA)
    compared = _compared(capsys.readouterr().out)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    chk = _check_limits()
    assert chk["gap_limit"] is None and "served_logit_gap_max" in compared
    assert compared["checked_tokens"] >= 30
    assert compared["sampled_tokens_longest"] > 40
    assert 0 < compared["selected_share_at_sampled_rows"] < 0.5
    for row, key in LIMIT_ROWS:
        assert compared[row] <= chk[key] < compared["control." + row], row
    # most recent keys instead of the indexer's: refused by the selection's
    # own row
    assert compared["control_recent.selection_miss_max"] > chk[
        "selection_miss_limit"]
    assert compared["control.passes_every_limit"] == 0
    assert compared["control_recent.passes_every_limit"] == 0
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report
        assert {"dsa_selected_share", "tick_host_ms.doc", "itl_p99_ms",
                "queue_wait_p90_ms", "prefill_busy_share"} <= set(res["metrics"])
        assert not {"dsa_prefill_roofline", "mla_step_roofline"} \
            & set(res["metrics"])
        assert 10 < res["metrics"]["dsa_selected_share"]["value"] < 60
    else:
        assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}


def _run_planted(harness, capsys, data=DATA, cell="tiny_glm.open"):
    res = harness.run(["--workload", cell, "--seed", "11",
                       "--seconds", "3", "--trace", "0", "--control", "0"],
                      require_tpu=False, data_dir=data)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "FAILED" in out
    return _compared(out)


@pytest.mark.parametrize("kind,data,cell", [
    ("latent", "data_glm", "tiny_glm.open"),
    ("kv", "data_keye_vl2", "tiny_keye.sat")])
def test_most_recent_keys_instead_of_the_indexers_is_not_correct(
        harness, capsys, monkeypatch, kind, data, cell):
    """Planted, in the one selection both kinds call: it takes the
    ``index_topk`` most recent visible positions, whatever the indexer
    scored; each kind's cell refuses it by the selection's own row."""
    def recent(scores, visible, topk):
        rank = jnp.broadcast_to(
            jnp.where(visible, jnp.arange(scores.shape[-1],
                                          dtype=jnp.float32), -jnp.inf),
            scores.shape)
        vals, idx = jax.lax.top_k(rank, min(topk, scores.shape[-1]))
        return idx.astype(jnp.int32), vals > -jnp.inf

    monkeypatch.setattr(sparse_select, "select", recent)
    data = os.path.join(BENCH, "tests", data)
    compared = _run_planted(harness, capsys, data, cell)
    traffic = os.path.join(data, "traffic", cell.split(".")[1] + ".json")
    assert compared["selection_miss_max"] > json.load(open(traffic))[
        "check"]["selection_miss_limit"]


def test_a_stale_index_key_is_not_correct(harness, capsys, monkeypatch):
    """Planted: a step writes its token's latent row and leaves the index
    key pool as it was, so later steps score what the block held before."""
    monkeypatch.setattr(sparse_select, "write_rows",
                        lambda pool, win, rows: pool)
    compared = _run_planted(harness, capsys)
    chk = _check_limits()
    assert any(compared[row] > chk[key] for row, key in LIMIT_ROWS)


def test_the_shared_expert_left_out_is_not_correct(harness, capsys,
                                                   monkeypatch):
    """Planted: an expert layer returns the routed part alone."""
    def routed_alone(self, p, u, live=None):
        if "router" not in p:
            return whole(self, p, u, live)
        zero = {n: jnp.zeros_like(p[n]) for n in ("shared_down",)}
        return whole(self, {**p, **zero}, u, live)

    whole = glm.GlmMath.ffn
    monkeypatch.setattr(glm.GlmMath, "ffn", routed_alone)
    compared = _run_planted(harness, capsys)
    assert compared["served_logit_gap_mean"] > _check_limits()["gap_mean_limit"]


def _reader(harness, name):
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "test_glm_reader_" + name)


def test_the_three_readers_on_a_planted_log(harness):
    """Known answers: ticks and a prefill planted in a window of their own,
    a trace summary made by hand."""
    cfg = json.load(open(os.path.join(BENCH, "configs", "glm5_l5_ep16.json")))
    pre = _bench_module("flops_bytes", "glm_moe_dsa_prefill.py")
    dec = _bench_module("flops_bytes", "glm_moe_dsa_decode_step.py")
    base = 700_000_000.0
    for k in range(4):
        t = base + 0.1 * k
        tracing.lane_record(
            "decode.tick", replica=0, seq=k + 1, n_active=2, n_adopted=0,
            n_finished=0, request_ids=(1, 2), kv_tokens=30_000,
            kv_visible=30_000, kv_selected=4096, experts_touched=40,
            t_loop=t, t_lock=t, t_disp0=t, t_disp1=t + 0.01, t_tok=t + 0.09,
            t_book=t + 0.095)
    # a prefill of 16,384 tokens, 2 s on the host, half of it in the trace
    tracing.lane_record(
        "prefill.batch", replica=0, seq=1, request_ids=(3,), n_tokens=16384,
        bucket=(1, 16384), radix_hit_tokens=0, t_start=base - 1.0,
        t_disp1=base - 1.0, t_ready=base + 1.0, t_lock=base + 1.0,
        t_commit1=base + 1.0, t_first=base + 1.0,
        kv_visible=16384 * 16385 // 2, kv_selected=pre.selected_pairs(16384, 2048))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"t0_abs": base, "window_s": 1.0, "config": cfg, "peaks": peaks,
           "chips": 1, "trace_host_window": (base, base + 0.5),
           "programs": {"step": r"^jit__step_fn", "prefill": r"^jit__prefill_fn"},
           "trace": {"chips": {0: {"modules": {
               "jit__step_fn": [0.020, 0.022, 0.021],
               "jit__prefill_fn": [0.5]}}}}}
    assert _reader(harness, "dsa_selected_share").read(obs) \
        == pytest.approx(100 * 4096 / 30_000)
    # a quarter of the prefill's 2 s lies in the traced half second
    want = 100 * pre.flops_needed(cfg, 16384) * 0.25 / 197e12 / 0.5
    assert _reader(harness, "dsa_prefill_roofline").read(obs) \
        == pytest.approx(want)
    assert 0 < want < 100
    need = dec.bytes_needed(cfg, 2, 30_000, 4096, 40)
    assert _reader(harness, "mla_step_roofline").read(obs) \
        == pytest.approx(100 * need / 819e9 / 0.021)
    # a window whose records carry no counters (the parent's, or any
    # other model's): nothing to read
    empty = dict(obs, t0_abs=base - 5000.0,
                 trace_host_window=(base - 5000.0, base - 4999.5))
    for name in ("dsa_selected_share", "dsa_prefill_roofline",
                 "mla_step_roofline"):
        assert _reader(harness, name).read(empty) is None
    bare = dict(obs, trace=None, trace_host_window=None)
    assert _reader(harness, "dsa_prefill_roofline").read(bare) is None
    assert _reader(harness, "mla_step_roofline").read(bare) is None

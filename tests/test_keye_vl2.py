"""Keye-VL-2.0's language model in the server: grouped-query attention
over the keys a learned indexer selects out of the paged K/V pools, an
index-key pool third in the block tables, over a bank of softmax-routed
experts, through the shared paged programs.

The reference is ``chipbench/references/keye_vl2.py`` (plain float32,
no cache, nothing of the program imported); a tiny preset whose
``index_topk`` (8) is smaller than the tests' sequences, so that the
selection is real at every size here.
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
from mxnet_tpu.models import keye_vl2 as keye
from mxnet_tpu.ops import paged_attention, sparse_select
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.serving.protocol import Request
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
TOPK = 8


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_keye_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "keye_vl2.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
            "sa_config": {"indexer_num_heads": cfg.index_n_heads,
                          "indexer_head_dim": cfg.index_head_dim,
                          "topk": cfg.index_topk},
            "vocab_size": cfg.vocab_size,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing, selection and attention are far from uniform)
    -> (net, the reference's weight tree, the reference's config)."""
    net = keye.keye_vl2_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    layers = []
    for l, lr in enumerate(net.layers):
        w = ref.init_layer(ref.layer_key(key, l), cfg, jnp.float32)
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
    """float32 against float32 in another order of operations (tiles, a
    mask for a sort, a cache): rounding alone."""
    assert got.shape == want.shape
    assert np.abs(got - want).max() < tol * np.abs(want).max()


# --- the mathematics, once -------------------------------------------------------

def test_gluon_forward_in_the_plain_form_equals_reference(ref, tiny):
    net, weights, cfg = tiny
    ids = np.random.RandomState(0).randint(1, 256, size=(2, 40))
    got = net(nd.array(ids, dtype="int32")).asnumpy()
    for b in range(2):
        _close(got[b], np.asarray(ref.forward(cfg, weights, ids[b])))
    # text's three position streams are equal: the ordinary rotation, to
    # the bit
    text = np.broadcast_to(np.arange(40, dtype=np.int32), (3, 2, 40))
    same = net(nd.array(ids, dtype="int32"),
               nd.array(text, dtype="int32")).asnumpy()
    assert (same == got).all()


def test_position_triples_that_differ_turn_each_frequency_by_its_stream(
        ref, tiny):
    """An image's tokens: ``p_t`` stands while ``p_h`` / ``p_w`` walk a
    grid, then text goes on.  Sections (2, 3, 3) of the 8 frequencies."""
    net, weights, cfg = tiny
    ids = np.random.RandomState(1).randint(1, 256, size=(1, 24))
    pos = np.zeros((3, 1, 24), np.int32)
    pos[:, 0, :4] = np.arange(4)
    grid = np.arange(16)
    pos[0, 0, 4:20], pos[1, 0, 4:20], pos[2, 0, 4:20] = \
        4, 4 + grid // 4, 4 + grid % 4
    pos[:, 0, 20:] = 8 + np.arange(4)
    got = net(nd.array(ids, dtype="int32"),
              nd.array(pos, dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward(cfg, weights, ids[0], positions=pos[:, 0]))
    _close(got, want)
    text = np.asarray(ref.forward(cfg, weights, ids[0]))
    assert np.abs(want - text).max() > 0.05 * np.abs(text).max()


def test_one_layer_equals_the_references_layer(ref, tiny):
    """A layer alone, so that a fault in it is not averaged away by the
    layers behind it."""
    net, weights, cfg = tiny
    x = np.random.RandomState(1).randn(1, 16, 64).astype(np.float32)
    text = np.broadcast_to(np.arange(16, dtype=np.int32), (3, 1, 16))
    got = net.layers[1](nd.array(x), nd.array(text, dtype="int32")) \
        .asnumpy()[0]
    with jax.default_matmul_precision("highest"):
        want, _bits, _m = ref.layer_forward(jnp.asarray(x[0]),
                                            weights["layers"][1], cfg)
    _close(got, np.asarray(want))


def test_the_references_blocks_and_key_extents_change_nothing(
        ref, tiny, a_sequence, monkeypatch):
    """The reference at the check's sizes runs in blocks of query rows whose
    groups read the keys up to their own end: at blocks of 8 rows in groups
    of 2 (three extents over 40 tokens) it says what it says in one block."""
    _net, weights, cfg = tiny
    seq, want, chosen = a_sequence
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    monkeypatch.setattr(ref, "KEY_GROUP", 2)
    got, picked = jax.jit(lambda w, ids: ref._forward(
        cfg, w, ids, ref.text_positions(40), False, "indexer", True))(
            weights, jnp.asarray(seq))
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
def test_prefill_then_decode_through_the_three_pools_equals_reference(
        tiny, a_sequence, t0):
    """Prompts shorter and longer than ``index_topk`` and than a bucket:
    the prefill under its selection's mask, the hand-over into the K, V
    and index-key pools, then every decode step selecting from the cache
    and reading those rows alone, against the reference's cache-less
    forward at every served token, past the point where a context
    exceeds ``topk``; and every step's selected set is the reference's
    own."""
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


def test_a_step_selects_through_the_block_table():
    """``window_select`` reads the index keys through the slots' tables:
    a live slot's set is the exact ``topk`` of its own visible positions,
    scored from the keys its blocks hold, whatever lies behind a sentinel
    entry or in a vacant slot's row."""
    rs = np.random.RandomState(4)
    s, bs, mb, nb, idim, ih, topk = 3, 4, 16, 40, 8, 2, 6
    pool = jnp.asarray(rs.randn(nb, 1, bs, 128), jnp.float32) \
        .at[..., idim:].set(0)
    tables = np.full((s, mb), nb, np.int32)
    tables[0, :5], tables[1, :3] = rs.permutation(nb)[:5], [7, 9, 30]
    pos = [17, 9, 61]                                   # slot 2 is vacant
    q_idx = jnp.asarray(rs.randn(s, ih, idim), jnp.float32)
    w_idx = jnp.asarray(rs.rand(s, ih), jnp.float32)
    win = paged_attention.window(pool, jnp.asarray(tables), jnp.asarray(pos),
                                 64, False)
    idx, valid = sparse_select.window_select(q_idx, w_idx, pool, win, topk)
    for i in range(2):
        keys = np.asarray(pool)[tables[i, :pos[i] // bs + 1], 0] \
            .reshape(-1, 128)[:pos[i] + 1]
        scores = np.asarray(sparse_select.index_scores(
            q_idx[i][None, None], w_idx[i][None, None],
            jnp.asarray(keys)[None]))[0, 0]
        want = np.argsort(-scores, kind="stable")[:topk]
        assert np.asarray(valid)[i].all()
        assert np.asarray(idx)[i].tolist() == want.tolist()


def test_a_step_scores_in_chunks_up_to_the_longest_live_slot(monkeypatch):
    """``window_select`` over chunks of 8 positions: what lies past the
    longest LIVE slot is neither read nor scored (a vacant slot's stale
    cursor does not count), and the selection is the one-chunk one."""
    rs = np.random.RandomState(4)
    s, bs, mb, nb, idim, ih, topk = 3, 4, 16, 40, 8, 2, 6
    pool = jnp.asarray(rs.randn(nb, 1, bs, 128), jnp.float32) \
        .at[..., idim:].set(0)
    tables = np.full((s, mb), nb, np.int32)
    tables[0, :5], tables[1, :3] = rs.permutation(nb)[:5], [7, 9, 30]
    pos = jnp.asarray([17, 9, 61])                      # slot 2 is vacant
    q_idx = jnp.asarray(rs.randn(s, ih, idim), jnp.float32)
    w_idx = jnp.asarray(rs.rand(s, ih), jnp.float32)
    win = paged_attention.window(pool, jnp.asarray(tables), pos, 64, False)
    whole = sparse_select.window_select(q_idx, w_idx, pool, win, topk)
    monkeypatch.setattr(sparse_select, "SCORE_CHUNK", 8)
    seen = []
    scores = sparse_select.index_scores

    def counting(q, w, keys):
        seen.append(keys.shape)
        return scores(q, w, keys)

    monkeypatch.setattr(sparse_select, "index_scores", counting)
    with jax.disable_jit():
        idx, valid = sparse_select.window_select(q_idx, w_idx, pool, win,
                                                 topk)
    # positions 0..17 of the longest live slot: three chunks of 8, not 8
    assert seen == [(s, 8, 128)] * 3
    for got, want in zip((idx, valid), whole):
        assert (np.asarray(got)[:2] == np.asarray(want)[:2]).all()
    assert np.asarray(valid)[:2].all() and (np.asarray(idx)[0] <= 17).all()


def test_the_three_forms_of_a_tiles_attention_agree(monkeypatch):
    """The plain form (the whole ``(T, T)`` scores, the sorted selection,
    K/V repeated), the masked form in tiles (the prefill's) in one group
    of keys or three, and the gather form (a step's, over the rows a
    sort names) at sizes where rows score nothing, rows choose and tiles
    past the sequences' ends are skipped."""
    rs = np.random.RandomState(2)
    b, t, nh, nkv, hd, ih, idim, topk = 2, 384, 4, 2, 8, 2, 8, 160
    q = jnp.asarray(rs.randn(b, nh, t, hd), jnp.float32)
    k = jnp.asarray(rs.randn(b, nkv, t, hd), jnp.float32)
    v = jnp.asarray(rs.randn(b, nkv, t, hd), jnp.float32)
    keys = jnp.asarray(rs.randn(b, t, idim), jnp.float32)
    q_idx = jnp.asarray(rs.randn(b, t, ih, idim), jnp.float32)
    w_idx = jnp.asarray(rs.rand(b, t, ih), jnp.float32)
    lengths = jnp.asarray([200, 330])
    with jax.default_matmul_precision("highest"):
        plain = np.asarray(sparse_select.kv_plain_causal_attention(
            q, k, v, q_idx, w_idx, keys, topk))
        for extent in (4096, 128):
            monkeypatch.setattr(sparse_select, "KEY_EXTENT", extent)
            tiled = np.asarray(sparse_select.kv_causal_attention(
                q, k, v, q_idx, w_idx, keys, lengths, topk))
            live = -(-330 // sparse_select.QUERY_TILE) \
                * sparse_select.QUERY_TILE
            _close(tiled[:, :live], plain[:, :live], 1e-5)
            assert not tiled[:, live:].any()
        # the gather form, a row at a time: the rows its sort names
        cols = jnp.arange(t)
        idx, valid = sparse_select.select(
            sparse_select.index_scores(q_idx, w_idx, keys),
            cols[None, :] <= cols[:, None], topk)
        rows_of = lambda a: a.transpose(0, 2, 1, 3).reshape(b, t, nkv * hd)
        for bi, row in ((0, 199), (1, 5), (1, 329)):
            got = sparse_select.gqa_selected_attention(
                q[bi, :, row][None], rows_of(k)[bi][idx[bi, row]][None],
                rows_of(v)[bi][idx[bi, row]][None], valid[bi, row][None])
            _close(np.asarray(got)[0], plain[bi, row], 1e-5)


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
    ("radix", dict(radix_cache=True), "selects over the prefix's index"),
    ("speculation", dict(draft_net="draft", spec_k=2), "one new token"),
    ("int8", dict(int8=True), "int8=True"),
    ("mesh", dict(), "index-key pool beside K and V"),
])
def test_options_refused_for_a_selecting_kv_engine(tiny, name, kw, says):
    from mxnet_tpu.serving.generative import REFUSALS

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
    assert str(exc.value) in [v for (t, _o), v in REFUSALS.items()
                              if t == "kv_select"]


def test_a_spec_says_which_layers_select():
    from mxnet_tpu.models.decoder import BlockDecoding, CacheSpec

    kw = dict(num_kv_heads=2, head_dim=16)
    spec = CacheSpec(("kv", "kv"), index_dim=8, select_topk=4, **kw)
    assert spec.kv_selecting and spec.kv_layers == 2
    # K and V of two heads of 16, and an index key stored a lane row wide
    assert spec.kv_bytes_per_block(4, 2) == 2 * 4 * 2 * (2 * 2 * 16 + 128)
    assert not CacheSpec(("kv", "kv"), **kw).kv_selecting
    # beside latent layers the selection is theirs: K/V layers read all
    mixed = CacheSpec(("latent", "kv"), latent_dim=8, index_dim=4,
                      select_topk=2, **kw)
    assert not mixed.kv_selecting
    for bad in (dict(index_dim=8), dict(select_topk=4),
                dict(index_dim=8, select_topk=4, passes=2),
                dict(index_dim=8, select_topk=4, decoding=BlockDecoding(
                    block_len=4, mask_id=7, steps=2, threshold=0.9))):
        with pytest.raises(mx.MXNetError):
            CacheSpec(("kv", "kv"), **kw, **bad)
    with pytest.raises(mx.MXNetError, match="select what a query reads"):
        CacheSpec(("state",), index_dim=8, select_topk=4, state_shape=(3,),
                  **kw)


def test_cache_bytes_by_kind_match_the_planner_and_count_the_padding(tiny):
    from mxnet_tpu.memory import plan_kv_pool

    net = tiny[0]
    srv = _server(net, num_slots=3, num_blocks=20)
    eng = srv.engine
    spec = eng.cache_spec
    assert spec.layers == ("kv",) * 3 and spec.kv_selecting
    assert (spec.kv_layers, spec.state_layers, spec.latent_layers) == (3, 0, 0)
    assert (spec.index_dim, spec.select_topk) == (8, 8)
    # a token's two KV heads of 16 in one stored row; an index key of 8
    # stored 128 lanes wide
    assert eng.kv_pack == 2 and eng._pool[0][0].shape == (20, 1, 4, 32)
    assert eng._pool[0][2].shape == (20, 1, 4, 128)
    assert sparse_select.index_pool_shape(7, 16, 64) == (7, 1, 16, 128)
    by_kind = eng.kv_pool_bytes(by_kind=True)
    assert by_kind == {"kv_blocks": 3 * 2 * 20 * 4 * 32 * 4, "slot_state": 0,
                       "index_key_blocks": 3 * 20 * 4 * 128 * 4}
    assert eng.kv_pool_bytes() == sum(by_kind.values()) == plan_kv_pool(
        3, 2, 16, num_blocks=20, block_size=4, index_dim=8)
    with srv:
        srv.generate(np.arange(1, 6), max_new_tokens=2)
        st = srv.stats()
    assert st["cache_bytes"] == by_kind
    assert (st["kv_layers"], st["latent_layers"]) == (3, 0)
    assert st["decode_attention"] == st["prefill_attention"] == "kv_sparse"
    mgr = srv.replicas[0].mgr
    assert mgr.kv_bytes_per_block == 3 * 4 * (2 * 32 + 128) * 4
    assert mgr.kv_bytes_per_block * 20 == eng.kv_pool_bytes()
    # at the published widths: 1,024 values of K and V and a lane row of
    # index key a token a layer, in bfloat16
    full = keye.KeyeDecoder.cache_spec(type("D", (), {
        "cfg": keye.KeyeVl2Config(num_layers=5)})())
    assert full.kv_bytes_per_block(16, 2) == 16 * 11_520


def test_lane_log_carries_what_was_visible_and_what_was_read(tiny):
    net = tiny[0]
    since = time.perf_counter()
    prompt = np.arange(1, 14)
    with _server(net, num_slots=1) as srv:
        srv.generate(prompt, max_new_tokens=6)
    pre = tracing.lane_log("prefill.batch", since=since)
    ticks = tracing.lane_log("decode.tick", since=since)
    assert pre[0]["prefill_attention"] == "kv_sparse"
    # 13 rows: 1 + 2 + .. + 13 visible, at most 8 read a row
    assert pre[0]["kv_visible"] == 13 * 14 // 2
    assert pre[0]["kv_selected"] == 8 * 9 // 2 + 5 * 8
    assert ticks[0]["decode_attention"] == "kv_sparse"
    assert [t["kv_visible"] for t in ticks] == [14, 15, 16, 17, 18]
    assert all(t["kv_selected"] == 8 for t in ticks)
    assert all(t["kv_visible"] == t["kv_tokens"] for t in ticks)
    # over the 3 layers in float32: an index key of 8 values a visible
    # position, K and V of 2 heads of 16 a selected one
    assert [t["index_key_bytes"] for t in ticks] \
        == [3 * 4 * 8 * n for n in (14, 15, 16, 17, 18)]
    assert all(t["selected_kv_bytes"] == 3 * 4 * 8 * 64 for t in ticks)
    assert pre[0]["selected_kv_bytes"] == 3 * 4 * 64 * pre[0]["kv_selected"]
    assert ticks[0]["experts_touched"] > 0


def test_compiled_program_names_are_the_benchmarks(tiny):
    fam = _bench_module("families", "keye_vl2.py")
    net = tiny[0]
    eng = _server(net).engine
    assert fam.PROGRAMS["step"] == r"^jit__step_fn"
    assert eng._step.__wrapped__.__name__ == "_step_fn"
    assert eng._prefill.__wrapped__.__name__ == "_prefill_fn"
    dec, w = eng._dec, eng._w
    text = jax.jit(lambda w, ids, t0: dec._prefill_rows_impl(w, ids, t0)) \
        .lower(w, jnp.zeros((1, 16), jnp.int32), jnp.asarray([16])) \
        .as_text(debug_info=True)
    for scope in ("dsa_scoring", "dsa_selection", "gqa_selected_attention",
                  "gqa_project", "moe_ffn"):
        assert scope in text, scope
    text = jax.jit(dec._step_blocks_impl).lower(
        w, eng._pool, jnp.asarray(eng._tables), jnp.zeros(3, jnp.int32),
        jnp.zeros(3, jnp.int32)).as_text(debug_info=True)
    for scope in ("dsa_scoring", "dsa_selection", "gqa_selected_attention"):
        assert scope in text, scope


# --- the benchmark's files ----------------------------------------------------------

def test_parameter_and_byte_tables_total_to_the_issues():
    pre = _bench_module("flops_bytes", "keye_vl2_prefill.py")
    dec = _bench_module("flops_bytes", "keye_vl2_decode_step.py")
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "keye_vl2_30b_a3b_l5.json")))
    attn, idx, expert, router = pre.linear_params(cfg)
    assert attn == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert idx == 2048 * 1024 + 2048 * 64 + 2048 * 16 == 2_260_992
    assert (expert, router) == (3 * 2048 * 768, 2048 * 128)
    outside, layer = dec.layer_params(cfg)
    assert layer / 1e6 == pytest.approx(625.38, abs=0.005)
    assert (layer - outside) / 1e6 == pytest.approx(603.98, abs=0.005)
    assert dec.weight_bytes(cfg) / 1e9 == pytest.approx(7.50, abs=0.005)
    assert dec.expert_bytes(cfg) == 9_437_184
    assert dec.cache_bytes_per_token(cfg) == 10_880
    # the published model, from the same table: "30B-A3B"
    top = 2 * 151936 * 2048 + 2048
    assert (48 * layer + top) / 1e9 == pytest.approx(30.64, abs=0.005)
    assert (48 * (outside + 8 * expert) + top // 2) / 1e9 \
        == pytest.approx(3.15, abs=0.005)
    # the model the cell builds has exactly these parameters
    net_cfg = keye.KeyeVl2Config(num_layers=5)
    n = 5 * sum(int(np.prod(s))
                for s in keye._layer_param_shapes(net_cfg).values()) + top
    assert 2 * n == dec.weight_bytes(cfg)
    # the pool as stored: 1,024 + 128 lanes a token a layer
    mix = json.load(open(os.path.join(
        BENCH, "traffic", "longctx_decode_sat_s16_o1k.json")))["system"]
    spec = keye.KeyeDecoder.cache_spec(type("D", (), {"cfg": net_cfg})())
    assert mix["num_blocks"] * spec.kv_bytes_per_block(mix["block_size"], 2) \
        == 393_216 * 11_520
    # operations: a 16k prompt's products, scores and attention
    n16 = 16384
    assert pre.selected_pairs(n16, 2048) == 2048 * 2049 // 2 + (n16 - 2048) * 2048
    assert pre.scored_pairs(n16, 2048) == n16 * (n16 + 1) // 2 - 2048 * 2049 // 2
    assert pre.scored_pairs(2000, 2048) == 0
    assert pre.flops_needed(cfg, n16) == 2 * pre.params_per_token(cfg) * n16 \
        + 5 * (2048 * pre.scored_pairs(n16, 2048)
               + 16384 * pre.selected_pairs(n16, 2048)) \
        + 2 * 151936 * 2048
    # a tick: fixed weights, the touched experts, index keys to each
    # position, the selected K and V rows, the new rows written
    need = dec.bytes_needed(cfg, active_slots=12, kv_visible=200_000,
                            kv_selected=12 * 2048, experts_touched=400)
    assert need == dec.fixed_weight_bytes(cfg) + 400 * dec.expert_bytes(cfg) \
        + 12 * 2048 * 2 + 5 * 2 * (200_000 * 64 + 12 * 2048 * 1024) \
        + 12 * 10_880
    assert dec.selection_bytes(cfg, 200_000, 12 * 2048) \
        == 5 * 2 * (200_000 * 64 + 12 * 2048 * 1024)
    assert need < dec.weight_bytes(cfg)


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "keye_vl2_30b_a3b_l5.json")))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == "Keye-VL-2.0-30B-A3B":
                row = json.loads(line)
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"}
    assert changed == set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["source"] == row["source_url"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"]
             if c["name"] == "keye_vl2_30b_a3b_l5"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    cell = [w for w in bench["workloads"]
            if w["name"] == "keye_vl2.longctx_decode_sat"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("keye_vl2_30b_a3b_l5", "longctx_decode_sat_s16_o1k", 1)
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in bench[g] if cell["name"] in m.get("workloads", ())}
    assert {"out_tok_per_s", "decode_occupancy", "decode_step_ms",
            "experts_touched_share", "expert_rows_max_over_mean",
            "sparse_gqa_step_roofline", "dsa_prefill_roofline.gqa",
            "dsa_selected_share.sat", "selection_bytes_share",
            "prefill_device_share.sat", "device_idle_share.decode"} <= reports
    assert not reports & {"ttft_p90_ms", "dsa_selected_share",
                          "mla_step_roofline", "selected_attn_roofline"}
    mix = json.load(open(os.path.join(
        BENCH, "traffic", "longctx_decode_sat_s16_o1k.json")))
    assert mix["driver"] == "closed_loop" and mix["order_seed"] == 23
    assert (mix["clients"], mix["distinct_sizes"]) == (32, 64)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 8192,
                                    "hi": 28672}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    sy = mix["system"]
    assert (sy["max_length"], sy["min_length"], sy["num_slots"],
            sy["max_batch"]) == (32768, 8192, 16, 1)
    assert sy["num_blocks"] * sy["block_size"] == 393_216
    # the longest request fits the check's padding
    assert mix["check"]["pad_tokens"] >= 28672 + 1024


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
    rows = {}
    for line in out.splitlines():
        if line.startswith("compared: "):
            name, rest = line[len("compared: "):].split(" = ")
            rows[name] = float(rest.split(" limit ")[0])
    return rows


DATA = os.path.join(BENCH, "tests", "data_keye_vl2")
LIMIT_ROWS = (("served_logit_gap_mean", "gap_mean_limit"),
              ("served_logit_gap_max_steady", "gap_steady_limit"),
              ("served_logit_gap_share_over_0.05", "gap_share_limit"),
              ("selection_miss_max", "selection_miss_limit"),
              ("selection_miss_first_layer", "first_layer_miss_limit"))


def _check_limits():
    return json.load(open(os.path.join(DATA, "traffic", "sat.json")))["check"]


@pytest.mark.parametrize("trace,seed", [
    (0, 4000000007), (1, 4000000007), (0, 2147483899), (0, 3000000019)])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace, seed):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_keye_vl2``:
    the new family, reference, traffic keys and readers at a tiny size,
    ``topk`` 8 under prompts of 8 to 40, a closed loop of 8 clients on 4
    slots."""
    res = harness.run(["--workload", "tiny_keye.sat", "--seed", str(seed),
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
    # the first layer's row reads every finished request, not the sample
    assert compared["first_layer_requests"] > chk["requests"]
    assert compared["control_recent.selection_miss_first_layer"] > chk[
        "first_layer_miss_limit"]
    assert compared["control.passes_every_limit"] == 0
    assert compared["control_recent.passes_every_limit"] == 0
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report
        assert {"dsa_selected_share.sat", "selection_bytes_share",
                "decode_occupancy", "tick_host_ms", "experts_touched_share",
                "expert_rows_max_over_mean", "free_slots_at_admit"} \
            <= set(res["metrics"])
        assert not {"sparse_gqa_step_roofline", "dsa_prefill_roofline.gqa",
                    "prefill_device_share.sat"} & set(res["metrics"])
        assert 10 < res["metrics"]["dsa_selected_share.sat"]["value"] < 60
        assert 0 < res["metrics"]["selection_bytes_share"]["value"] < 50
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def _run_planted(harness, capsys):
    res = harness.run(["--workload", "tiny_keye.sat", "--seed", "11",
                       "--seconds", "3", "--trace", "0", "--control", "0"],
                      require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "FAILED" in out
    return _compared(out)


def test_a_stale_index_key_is_not_correct(harness, capsys, monkeypatch):
    """Planted: a step writes its token's K and V rows and leaves the index
    key pool as it was, so later steps score what the block held before."""
    monkeypatch.setattr(sparse_select, "write_rows",
                        lambda pool, win, rows: pool)
    compared = _run_planted(harness, capsys)
    chk = _check_limits()
    assert any(compared[row] > chk[key] for row, key in LIMIT_ROWS)
    # the first layer's row alone refuses it, over every finished request
    assert compared["selection_miss_first_layer"] > chk[
        "first_layer_miss_limit"]


def test_a_control_that_only_the_first_layers_row_refuses(harness):
    """A control passes every limit only if the first layer's row lets it:
    ``_first_layer_rows`` takes ``passes_every_limit`` 1 down to 0 where the
    control's first-layer miss is over the limit, and leaves a 0 alone."""
    fam = _bench_module("families", "keye_vl2.py")
    cell = fam.Cell.__new__(fam.Cell)
    cell.mix = {"check": {"first_layer_miss_limit": 0.1, "pad_tokens": 8}}
    cell.cfg, cell.seed = {}, 3
    want = np.zeros((1, 8), bool)
    want[0, :4] = True

    class Ref:
        @staticmethod
        def first_layer_selected(cfg, seed, ids, at, lowp=False,
                                 select="indexer"):
            out = want.copy()
            if lowp:                    # the float8 control misses a quarter
                out[0, 0], out[0, 5] = False, True
            elif select == "recent":    # the recent keys miss a half
                out[0, :2], out[0, 5:7] = False, True
            return out

    class Req:
        selected = (5, np.asarray([[0, 1, 2, 3, -1]]))
        future = type("F", (), {"result": staticmethod(
            lambda: np.arange(6, dtype=np.int32))})

    cell.ref = Ref
    cell._rows = [{"finished": True, "_rec": {"req": Req}},
                  {"finished": False, "_rec": {"req": None}}]
    base = [("served_logit_gap_mean", 0.0, 0.1),
            ("control.passes_every_limit", 1.0, 0.0),
            ("control_recent.passes_every_limit", 0.0, 0.0)]
    rows = {n: (v, l) for n, v, l in cell._first_layer_rows(base, True)}
    assert rows["selection_miss_first_layer"] == (0.0, 0.1)
    assert rows["first_layer_requests"] == (1.0, None)
    assert rows["control.selection_miss_first_layer"] == (0.25, None)
    assert rows["control_recent.selection_miss_first_layer"] == (0.5, None)
    assert rows["selection_miss_first_layer_median"] == (0.0, None)
    assert rows["control.selection_miss_first_layer_min"] == (0.25, None)
    assert rows["control.passes_every_limit"] == (0.0, 0.0)
    assert rows["control_recent.passes_every_limit"] == (0.0, 0.0)
    # a control whose first layer agrees keeps what the other rows said
    Ref.first_layer_selected = staticmethod(
        lambda cfg, seed, ids, at, **kw: want.copy())
    rows = {n: (v, l) for n, v, l in cell._first_layer_rows(base, True)}
    assert rows["control.passes_every_limit"] == (1.0, 0.0)
    # without a control the rows of one are left out
    assert len(cell._first_layer_rows(base, False)) == len(base) + 3


@pytest.mark.parametrize("kw", [{}, {"lowp": True}, {"select": "recent"}],
                         ids=["float32", "float8", "recent"])
def test_the_first_layers_set_is_the_full_forwards(ref, kw, monkeypatch):
    """``first_layer_selected`` (an embedding and the indexer's three products)
    names what layer 0 of the check's whole forward pass selects at the same
    row, sound and under either control, with blocks of 8 query rows."""
    cfg = json.load(open(os.path.join(DATA, "configs", "tiny_keye.json")))
    ids = np.random.RandomState(8).randint(1, 256, size=(2, 48))
    at = [29, 47]
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    ref._programs.cache_clear()             # traced under the blocks of 8
    try:
        got = ref.first_layer_selected(cfg, 5, ids, at, **kw)
        want = ref.forward_rows(cfg, 5, ids, np.asarray([[0, 29], [1, 47]]),
                                selected_at=at, **kw)[1][0]
    finally:
        ref._programs.cache_clear()
    assert got.shape == (2, 48) and (got == want).all()
    assert got.sum(axis=-1).tolist() == [8, 8]
    assert not got[0, 30:].any()


def _reader(harness, name):
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "test_keye_reader_" + name.replace(".", "_"))


def test_the_five_readers_on_a_planted_log(harness):
    """Known answers: ticks and a prefill planted in a window of their own,
    a trace summary made by hand."""
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "keye_vl2_30b_a3b_l5.json")))
    pre = _bench_module("flops_bytes", "keye_vl2_prefill.py")
    dec = _bench_module("flops_bytes", "keye_vl2_decode_step.py")
    base = 800_000_000.0
    sel = dec.selection_bytes(cfg, 30_000, 4096)
    for k in range(4):
        t = base + 0.1 * k
        tracing.lane_record(
            "decode.tick", replica=0, seq=k + 1, n_active=2, n_adopted=0,
            n_finished=0, request_ids=(1, 2), kv_tokens=30_000,
            kv_visible=30_000, kv_selected=4096, experts_touched=80,
            index_key_bytes=5 * 2 * 64 * 30_000,
            selected_kv_bytes=5 * 2 * 1024 * 4096,
            t_loop=t, t_lock=t, t_disp0=t, t_disp1=t + 0.01, t_tok=t + 0.09,
            t_book=t + 0.095)
    # a prefill of 16,384 tokens, 2 s on the host, half of it in the trace
    tracing.lane_record(
        "prefill.batch", replica=0, seq=1, request_ids=(3,), n_tokens=16384,
        bucket=(1, 16384), radix_hit_tokens=0, t_start=base - 1.0,
        t_disp1=base - 1.0, t_ready=base + 1.0, t_lock=base + 1.0,
        t_commit1=base + 1.0, t_first=base + 1.0,
        kv_visible=16384 * 16385 // 2,
        kv_selected=pre.selected_pairs(16384, 2048), index_key_bytes=1,
        selected_kv_bytes=1)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"t0_abs": base, "window_s": 1.0, "config": cfg, "peaks": peaks,
           "chips": 1, "trace_host_window": (base, base + 0.5),
           "programs": {"step": r"^jit__step_fn", "prefill": r"^jit__prefill_fn"},
           "trace": {"chips": {0: {"busy_s": 0.4, "modules": {
               "jit__step_fn": [0.020, 0.022, 0.021],
               "jit__prefill_fn": [0.1]}}}}}
    assert _reader(harness, "dsa_selected_share.sat").read(obs) \
        == pytest.approx(100 * 4096 / 30_000)
    need = dec.bytes_needed(cfg, 2, 30_000, 4096, 80)
    assert _reader(harness, "selection_bytes_share").read(obs) \
        == pytest.approx(100 * sel / need)
    assert _reader(harness, "sparse_gqa_step_roofline").read(obs) \
        == pytest.approx(100 * need / 819e9 / 0.021)
    assert 0 < 100 * need / 819e9 / 0.021 < 100
    # a quarter of the prefill's 2 s lies in the traced half second
    want = 100 * pre.flops_needed(cfg, 16384) * 0.25 / 197e12 / 0.1
    assert _reader(harness, "dsa_prefill_roofline.gqa").read(obs) \
        == pytest.approx(want)
    assert 0 < want < 100
    assert _reader(harness, "prefill_device_share.sat").read(obs) \
        == pytest.approx(25.0)
    # a window whose records carry no counters (the parent's, or any
    # other model's), another family's configuration, no trace: nothing
    # to read
    names = ("dsa_selected_share.sat", "selection_bytes_share",
             "sparse_gqa_step_roofline", "dsa_prefill_roofline.gqa")
    empty = dict(obs, t0_abs=base - 5000.0,
                 trace_host_window=(base - 5000.0, base - 4999.5))
    other = dict(obs, config=json.load(open(os.path.join(
        BENCH, "configs", "glm5_l5_ep16.json"))))
    for name in names:
        assert _reader(harness, name).read(empty) is None
    for name in names[1:]:
        assert _reader(harness, name).read(other) is None
    bare = dict(obs, trace=None, trace_host_window=None)
    for name in names[2:] + ("prefill_device_share.sat",):
        assert _reader(harness, name).read(bare) is None


def test_scope_times_on_a_planted_trace(monkeypatch):
    """``tools/scope_times.py``: an instruction goes to the program whose
    module event holds it and to the first scope its stats name; control
    flow and what lies outside every program are left out."""
    import types

    import jax.profiler

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import scope_times

    def ev(name, start, end, **stats):
        return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                     duration_ns=end - start,
                                     stats=list(stats.items()))

    def op(n, start, end, scope):
        return ev(f"%{n} = f32[8] fusion(%p)", start, end,
                  tf_op=f"jit(_step_fn)/jit(main)/{scope}/dot_general")

    plane = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Modules", events=[
            ev("jit__step_fn(123)", 0, 1000), ev("jit__step_fn(123)", 2000, 3000),
            ev("jit__prefill_fn(9)", 4000, 9000)]),
        types.SimpleNamespace(name="XLA Ops", events=[
            op("fusion.1", 0, 400, "dsa_scoring"),
            op("sort.2", 400, 700, "dsa_selection"),
            op("while.3", 0, 1000, "dsa_scoring"),          # control flow
            op("fusion.4", 2100, 2600, "layers/mlp"),
            op("fusion.5", 1500, 1600, "dsa_scoring"),      # in no program
            op("fusion.6", 4000, 8000, "dsa_scoring")])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(
        lambda path: types.SimpleNamespace(planes=[host, plane])))
    got = scope_times.by_scope("x", ["dsa_scoring", "dsa_selection"])
    assert got["stat_keys"] == ["tf_op"]
    step = got["jit__step_fn"]
    assert step["calls"] == 2 and step["seconds"] == pytest.approx(1.2e-6)
    assert step["by_scope"] == pytest.approx(
        {"dsa_scoring": 4e-7, "dsa_selection": 3e-7, "other": 5e-7})
    assert got["jit__prefill_fn"]["by_scope"] == pytest.approx(
        {"dsa_scoring": 4e-6})
    assert step["by_op"] == pytest.approx(
        {"fusion.1": 4e-7, "sort.2": 3e-7, "fusion.4": 5e-7})
    # events that name no scope go by their instruction in the compiled text
    hlo = scope_times.scopes_of(
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(_step_fn)/gqa_selected_attention/dot" '
        'source_file="x.py"}\n  ROOT %sort.2 = f32[8] sort(%a), '
        'metadata={op_name="jit(_step_fn)/layers/mlp/sort"}\n',
        ["gqa_selected_attention"])
    assert hlo == {"fusion.4": "gqa_selected_attention"}

"""SDAR-MoE (``models/sdar.py``) against its plain reference
(``chipbench/references/sdar.py``) at a tiny size on the CPU: the Gluon
forward under the block mask, prefill of whole blocks then block passes
through ``GenerativeServer``'s paged cache, the block window of the
paged kernel and of the gather path, the prefill mask dense and flash,
tokens AND commit order through the lanes against the reference's own
generation loop in both branches of the commit rule, what the engine
refuses, the lane-log fields, the benchmark's files, a rehearsal of the
cell and the planted faults that its check must refuse (a causal order
inside a block, a skipped store pass, a wrong commit rule, a control the
limits cannot tell from the program).

hidden 64, 3 layers, 4 query / 2 KV heads of 16, 16 experts top 8,
vocabulary 256 (mask id 255), blocks of 4; float32 weights.
"""
import hashlib
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
from mxnet_tpu.models import decoder, sdar
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.serving import ServerConfig
from mxnet_tpu.serving.protocol import Request
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
BL = 4


def _bench_module(*parts):
    path = os.path.join(BENCH, *parts)
    name = "test_sdar_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "sdar.py")


def _ref_cfg(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.hidden_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "head_dim": cfg.head_dim,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "num_hidden_layers": cfg.num_layers,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "confidence_threshold": cfg.confidence_threshold,
            "mask_token_id": cfg.mask_token_id,
            "initializer_range": 0.3, "torch_dtype": "float32"}


def _net_and_weights(ref, seed=3, head_scale=1.0, **overrides):
    """A tiny net filled with the reference's seeded weights (Normal(0,
    0.3), so that routing and attention are far from uniform) -> (net,
    the reference's weight tree).  ``head_scale`` sharpens the logits:
    at 6 many confidences pass the threshold of 0.9."""
    net = sdar.sdar_moe_tiny(**overrides)
    net.initialize()
    cfg = _ref_cfg(net.config)
    key = jax.random.PRNGKey(seed)
    top = ref.init_top(ref.top_key(key), cfg, jnp.float32)
    top["head"] = top["head"] * head_scale
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


@pytest.fixture(scope="module")
def peaked(ref):
    return _net_and_weights(ref, seed=5, head_scale=6.0)


def _server(net, **kw):
    cfg = dict(max_batch=2, max_length=64, min_length=8, num_slots=3,
               block_size=4)
    cfg.update(kw)
    return serving.GenerativeServer(net, ServerConfig(**cfg))


# --- the mathematics, once ----------------------------------------------------

def test_gluon_forward_under_the_block_mask_equals_reference(ref, tiny):
    net, weights, cfg = tiny
    ids = np.random.RandomState(0).randint(0, 255, size=(2, 22))
    ids[:, -3:] = 255                         # undecided positions
    got = net(nd.array(ids, dtype="int32")).asnumpy()
    for b in range(2):
        want = np.asarray(ref.forward(cfg, weights, ids[b]))
        assert np.abs(got[b] - want).max() < 2e-4 * np.abs(want).max()
    # and it is not the causal model: a row sees the rest of its block
    causal = np.asarray(ref.forward(cfg, weights, ids[0],
                                    mask=np.tril(np.ones((22, 22), bool))))
    assert np.abs(got[0] - causal).max() > 1e-2 * np.abs(causal).max()


@pytest.mark.parametrize("flash", [False, True])
def test_prefill_mask_dense_and_flash_equal_the_reference(ref, flash):
    """``Causal(block=4)`` through ``masked_attention`` and through the
    flash forward kernel (interpreter) against softmax under the
    reference's ``block_mask``, with true lengths of whole blocks."""
    from mxnet_tpu.ops import flash_attention as fa

    rs = np.random.RandomState(7)
    b, h, hkv, t, hd = 2, 4, 2, 256, 64
    q = jnp.asarray(rs.randn(b, h, t, hd), jnp.float32)
    k = jnp.asarray(rs.randn(b, hkv, t, hd), jnp.float32)
    v = jnp.asarray(rs.randn(b, hkv, t, hd), jnp.float32)
    lengths = np.asarray([256, 132], np.int32)
    mask = ref.block_mask(t, BL)
    s = np.einsum("bhqd,bhtd->bhqt", np.asarray(q),
                  np.repeat(np.asarray(k), 2, 1)) / np.sqrt(hd)
    s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqt,bhtd->bhqd", p / p.sum(-1, keepdims=True),
                     np.repeat(np.asarray(v), 2, 1))
    if flash:
        view = decoder.Causal(t, lengths=jnp.asarray(lengths), block=BL)
        got = fa._prefill_flash_attention(q, k, v, view.lengths,
                                          interpret=True, span=BL)
        got = np.asarray(got)
    else:
        got = np.asarray(decoder.Causal(t, block=BL).attend(q, k, v)[0]) \
            .transpose(0, 2, 1, 3)
    for i, n in enumerate(lengths):
        assert np.abs(got[i, :, :n] - want[i, :, :n]).max() < 2e-5
    # a causal order inside a block is another answer
    causal = np.asarray(decoder.Causal(t).attend(q, k, v)[0]) \
        .transpose(0, 2, 1, 3)
    assert np.abs(causal - want).max() > 1e-2


@pytest.mark.parametrize("kernel", [False, True])
def test_block_window_kernel_and_gather_equal_dense(kernel):
    """The 4 columns of a block all see ``pos0 + 4`` rows of their slot:
    the paged kernel's block bound (interpreter) and the gather path's
    mask against dense softmax over each slot's own rows."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(11)
    s, h, hkv, hd, bs, mb = 3, 8, 2, 128, 16, 4
    nb = s * mb
    kp = jnp.asarray(rs.randn(nb, hkv, bs, hd), jnp.float32)
    vp = jnp.asarray(rs.randn(nb, hkv, bs, hd), jnp.float32)
    tables = np.arange(nb, dtype=np.int32).reshape(s, mb)
    tables[2] = nb                                # a vacant slot
    pos0 = np.asarray([20, 0, 0], np.int32)
    q = jnp.asarray(rs.randn(s, BL, h, hd), jnp.float32)
    if kernel:
        got = pa._paged_decode_attention(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(pos0 + 1),
            interpret=pltpu.InterpretParams(), block=True)
    else:
        pw = jnp.asarray(pos0)[:, None] + jnp.arange(BL)[None]
        win = pa.window(kp, jnp.asarray(tables), pw, mb * bs, False,
                        block=True)
        got = pa.window_attention(q.transpose(0, 2, 1, 3), kp, vp, win)
    got = np.asarray(got)
    for i in (0, 1):
        n = pos0[i] + BL
        kk = np.asarray(kp)[tables[i]].transpose(1, 0, 2, 3) \
            .reshape(hkv, mb * bs, hd)[:, :n]
        vv = np.asarray(vp)[tables[i]].transpose(1, 0, 2, 3) \
            .reshape(hkv, mb * bs, hd)[:, :n]
        sc = np.einsum("khd,htd->hkt", np.asarray(q)[i],
                       np.repeat(kk, h // hkv, 0)) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hkt,htd->khd", p / p.sum(-1, keepdims=True),
                         np.repeat(vv, h // hkv, 0))
        assert np.abs(got[i] - want).max() < 2e-5, i
    # the verify window of the same call is causal inside: another answer
    causal = pa._paged_decode_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(pos0 + 1),
        interpret=pltpu.InterpretParams()) if kernel else None
    if kernel:
        assert np.abs(np.asarray(causal)[0, 0] - got[0, 0]).max() > 1e-3
        assert np.abs(np.asarray(causal)[0, -1] - got[0, -1]).max() < 2e-5


# --- through the paged cache -----------------------------------------------------

def _block_pass(eng, slot, ids, pos0):
    """One pass of the decoder under the engine's step program over
    ``slot``'s block -> its logits (B, vocab); the pool takes the
    block's K/V as the program's pass leaves it."""
    toks = np.zeros((eng.num_slots, BL), np.int32)
    pos = np.zeros(eng.num_slots, np.int32)
    toks[slot], pos[slot] = ids, pos0
    lg, eng._pool, _c = eng._dec._verify_blocks_impl(
        eng._w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(toks),
        jnp.asarray(pos),
        paged_kernel=eng.decode_attention == "paged_kernel")
    return np.asarray(lg)[slot]


def _admit(eng, prompt, slot=0):
    """Prefill through the engine's own program and commit."""
    p = len(prompt)
    lb = max(8, 1 << (p - 1).bit_length())
    ids = np.zeros((1, lb), np.int32)
    ids[0, :p] = prompt
    first, rows = eng.prefill_rows(ids, np.asarray([p], np.int32))
    first, _ = eng.split_fetch(np.asarray(first), 1)
    blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
    eng.commit_rows(rows, np.asarray([slot]), [blocks], np.asarray([p]),
                    first)
    return first[0]


@pytest.mark.parametrize("p", [1, 3, 4, 5, 13, 16])
def test_prefill_then_block_passes_equal_the_cacheless_reference(ref, tiny, p):
    """Every remainder of the prompt over the block length: the prompt's
    whole blocks are prefilled, the rest opens the first block beside
    masks; then three blocks, each passed with masks, half decided and
    decided (the pass that stores), against the reference's forward of
    the same states without a cache."""
    net, weights, cfg = tiny
    eng = _server(net).engine
    rs = np.random.RandomState(100 + p)
    prompt = rs.randint(1, 255, size=p)
    opening = _admit(eng, prompt)
    p0 = p // BL * BL
    assert eng._pos[0] == p0
    assert opening.tolist() == prompt[p0:].tolist() + [255] * (BL - p % BL)
    assert eng._blk_masked[0].tolist() == [j >= p % BL for j in range(BL)]
    seq = list(prompt[:p0])
    block = opening.copy()
    for _ in range(3):
        final = np.where(block == 255, rs.randint(1, 255, size=BL), block)
        half = np.where(np.arange(BL) % 2 == 1, final, block)
        for state in (block, half, final):
            got = _block_pass(eng, 0, state, len(seq))
            # one length for every state (a compile a length): what lies
            # behind the block is masks, which its rows do not see
            whole = np.full(32, 255)
            whole[:len(seq) + BL] = seq + list(state)
            want = np.asarray(ref.forward(cfg, weights, whole))[
                len(seq):len(seq) + BL]
            assert np.abs(got - want).max() < 5e-4 * np.abs(want).max()
        seq += list(final)
        block = np.full(BL, 255)


def _generate(srv, prompts, max_new):
    reqs = [Request(prompt_ids=np.asarray(p, np.int32), max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    for r in reqs:
        srv._submit(r)
    return reqs, [r.future.result(120) for r in reqs]


@pytest.mark.parametrize("which", ["seeded", "peaked"])
def test_tokens_and_commit_order_through_the_lanes_follow_the_reference(
        ref, tiny, peaked, which):
    """Seeded weights never reach the threshold: one commit a pass, by
    confidence (the static schedule).  The peaked model passes it:
    several tokens a pass (the dynamic branch).  Either way the served
    tokens and every commit ``(position, token, pass)`` in order are the
    reference's own generation loop's; prompts of every remainder, some
    ending inside a block."""
    net, weights, cfg = tiny if which == "seeded" else peaked
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 255, size=n) for n in (1, 3, 8, 14, 17)]
    max_new = [5, 7, 4, 6, 9]
    with _server(net) as srv:
        reqs, outs = _generate(srv, prompts, max_new)
    most = 0
    for p, n, req, out in zip(prompts, max_new, reqs, outs):
        want, commits = ref.generate(cfg, weights, p, n)
        assert out[:len(p)].tolist() == p.tolist()
        assert out[len(p):].tolist() == want.tolist()
        assert req.commits == commits
        per_pass = {}
        for pos, _tok, step in commits:
            per_pass[pos // BL, step] = per_pass.get((pos // BL, step), 0) + 1
        most = max(most, max(per_pass.values()))
        assert req.t_commit <= req.t_handoff <= req.t_first <= req.t_done
    assert most == 1 if which == "seeded" else most > 1


def test_a_pool_that_parks_gives_the_block_ticks_tokens_and_commits(tiny):
    """Blocks granted as a request grows, under the block tick: a pass writes
    its whole block, so a slot is granted the K/V block that holds it before
    the block's first pass.  The same requests through a pool at parity and
    through one a little over the largest maximum: the same tokens and the
    same commits ``(position, token, pass)`` request by request (a parked
    slot's pass is left out and made again, its cursor and its block's pass
    count untouched), parked slots counted in the small pool's lane log and
    none in the large one's."""
    net, _weights, _cfg = tiny
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 255, size=n) for n in (1, 3, 8, 14, 17)]
    max_new = [30, 28, 35, 26, 20]          # maxima of 8 to 11 blocks of 4
    runs = []
    for num_blocks in (None, 12):           # parity: 3 slots x 16
        since = time.perf_counter()
        with _server(net, num_blocks=num_blocks) as srv:
            reqs, outs = _generate(srv, prompts, max_new)
            srv.replicas[0].mgr.check()
            kv = srv.stats()["kv_cache"]
        ticks = tracing.lane_log("decode.tick", since=since)
        runs.append((reqs, outs, ticks, kv))
    (reqs_w, outs_w, ticks_w, kv_w), (reqs_s, outs_s, ticks_s, kv_s) = runs
    for p, n, a, b, ra, rb in zip(prompts, max_new, outs_w, outs_s,
                                  reqs_w, reqs_s):
        assert len(a) == len(p) + n and a.tolist() == b.tolist()
        assert ra.commits == rb.commits
    assert all(t["n_parked"] == 0 for t in ticks_w)
    assert kv_w["parked_slot_ticks"] == 0
    assert kv_w["unsafe_refusals"] == {"admit": 0, "grant": 0}
    parked = sum(t["n_parked"] for t in ticks_s)
    assert 0 < parked <= kv_s["parked_slot_ticks"]
    # a pass with no live row carried none but slots whose requests the
    # pass before it, not yet fetched as it was queued, had just ended
    for before, t in zip(ticks_s, ticks_s[1:]):
        assert t["n_active"] >= 1 or (t["ahead"] and before["n_finished"])
    assert all(t["n_active"] * BL == t["rows"] for t in ticks_s)
    assert kv_s["peak_blocks_in_use"] <= 12
    for kv in (kv_w, kv_s):
        assert kv["admits"] == kv["evictions"] == 5 and kv["grants"] > 0


def test_a_request_that_ends_inside_a_block(ref, tiny):
    """Six tokens behind a prompt of 7: the output ends at position 12,
    the first of its block; the request finishes with the pass that
    commits it, and what the block's other positions hold by then is in
    the commit record and not in the answer."""
    net, weights, cfg = tiny
    prompt = np.arange(1, 8)
    with _server(net) as srv:
        reqs, outs = _generate(srv, [prompt], [6])
    want, commits = ref.generate(cfg, weights, prompt, 6)
    assert outs[0][7:].tolist() == want.tolist() and len(outs[0]) == 13
    assert reqs[0].commits == commits
    assert max(pos for pos, _t, _s in commits) >= 12
    assert {pos for pos, _t, _s in commits} >= set(range(7, 13))


def test_freed_slot_readmitted_gives_a_fresh_servers_answer(tiny):
    """One slot: the second request reuses the first's slot and blocks."""
    net = tiny[0]
    rs = np.random.RandomState(9)
    first, second = rs.randint(1, 255, size=19), rs.randint(1, 255, size=10)
    with _server(net, num_slots=1) as srv:
        reqs, outs = _generate(srv, [first, second], [9, 7])
    with _server(net, num_slots=1) as srv:
        fresh_reqs, fresh = _generate(srv, [second], [7])
    assert outs[1].tolist() == fresh[0].tolist()
    assert reqs[1].commits == fresh_reqs[0].commits


def test_a_block_at_max_length(ref, tiny):
    """Prompt and output fill the cache to its last position: the last
    block is written and read whole."""
    net, weights, cfg = tiny
    prompt = np.random.RandomState(3).randint(1, 255, size=22)
    with _server(net, max_length=32, num_slots=1) as srv:
        reqs, outs = _generate(srv, [prompt], [10])
        srv.replicas[0].mgr.check()
    want, commits = ref.generate(cfg, weights, prompt, 10)
    assert outs[0][22:].tolist() == want.tolist()
    assert reqs[0].commits == commits
    with pytest.raises(mx.MXNetError) as exc:
        _server(net, max_length=30)
    assert "whole blocks" in str(exc.value)


# --- what is refused, loudly ---------------------------------------------------

def _draft():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    return net


@pytest.mark.parametrize("name,kw,says", [
    ("radix", dict(radix_cache=True), "block decoder"),
    ("speculation", dict(draft_net="draft", spec_k=2), "left-to-right"),
    ("int8", dict(int8=True), "int8=True"),
    ("mesh", dict(), "mesh"),
])
def test_options_refused_for_a_block_decoder(tiny, name, kw, says):
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


def test_a_block_decoder_with_a_state_layer_is_refused(tiny, monkeypatch):
    """The block tick makes a pass again for a parked slot and carries a slot
    a pass past its request's end: a state layer's step is not idempotent, so
    such a model (none exists) is refused by name, not served by a serial
    tick kept for it."""
    real = sdar.SdarDecoder.cache_spec

    def with_a_state_layer(self):
        spec = real(self)
        return decoder.CacheSpec(
            layers=("state",) + spec.layers[1:],
            num_kv_heads=spec.num_kv_heads, head_dim=spec.head_dim,
            state_shape=(3, 8), decoding=spec.decoding)

    monkeypatch.setattr(sdar.SdarDecoder, "cache_spec", with_a_state_layer)
    with pytest.raises(mx.MXNetError, match="not idempotent"):
        _server(tiny[0])


def test_no_sampling_option_exists_to_refuse():
    """Greedy is the only decoding ``ServerConfig`` has: a sampling option
    that arrives has to be refused for a block decoder by name."""
    import inspect

    params = set(inspect.signature(ServerConfig.__init__).parameters)
    assert not params & {"temperature", "top_k", "top_p", "sampling",
                         "greedy", "seed"}


# --- the lane log, the totals, the programs' names -------------------------------

def test_lane_log_fields_and_totals_of_a_block_decoder(tiny):
    net = tiny[0]
    since = time.perf_counter()
    with _server(net, num_slots=2, max_batch=1) as srv:
        _reqs, outs = _generate(srv, [np.arange(1, 1 + n) for n in (3, 9, 12)],
                                [6, 8, 5])
        stats = srv.stats()
    ticks = tracing.lane_log("decode.tick", since=since)
    batches = tracing.lane_log("prefill.batch", since=since)
    assert ticks and len(batches) == 3
    first = ticks[0]
    assert first["decoding"] == stats["decoding"] == "block_diffusion"
    assert first["block_decoding"] == stats["block_decoding"] == {
        "block_len": 4, "mask_id": 255, "steps": 4, "threshold": 0.9}
    assert "decoding" not in ticks[-1]
    committed = stored = passes = 0
    for rec in ticks:
        assert rec["block_len"] == 4
        assert rec["rows"] == rec["n_active"] * 4
        assert 0 <= rec["n_store"] <= rec["n_active"]
        assert 0 <= rec["committed"] <= rec["rows"]
        assert rec["kv_tokens"] % 4 == 0 and rec["kv_tokens"] >= rec["rows"]
        # the expert counters count the block's rows of slots that hold
        # a block: 8 experts a row, 3 layers
        n = round(rec["expert_rows_mean"] * rec["experts_touched"])
        assert n % (4 * 8 * 3) == 0 and n >= rec["rows"] * 8 * 3
        committed += rec["committed"]
        stored += rec["n_store"]
        passes += rec["block_passes"]
    # a prefill counts the prompt's whole blocks only
    for rec, n_prompt in zip(batches, (3, 9, 12)):
        assert rec["expert_rows_mean"] * rec["experts_touched"] \
            == pytest.approx(n_prompt // 4 * 4 * 8 * 3)
    tot = stats["blocks"]
    assert tot == {"block_passes": passes, "blocks_committed": stored,
                   "committed_tokens": committed}
    assert committed >= 6 + 8 + 5 and stored >= 2
    # one commit a pass on seeded weights: a whole block takes 4 passes
    # and the one that stores it
    assert passes <= 5 * stored
    assert stats["decode_steps"] == len(ticks)


def test_a_next_token_server_says_so():
    from mxnet_tpu.models.llama import llama_tiny

    net = llama_tiny()
    net.initialize()
    since = time.perf_counter()
    with serving.GenerativeServer(net, ServerConfig(
            max_batch=2, max_length=64, min_length=8, num_slots=2)) as srv:
        srv.generate(np.arange(1, 7), max_new_tokens=3)
        st = srv.stats()
    tick = tracing.lane_log("decode.tick", since=since)[0]
    assert tick["decoding"] == st["decoding"] == "next_token"
    assert "block_len" not in tick and "blocks" not in st \
        and "block_decoding" not in st


def _lowered(eng):
    ids = np.ones((1, 8), np.int32)
    t0s = np.full(1, 6, np.int32)
    out = {"prefill": eng._prefill.lower(eng._w, eng._dev(ids),
                                         eng._dev(t0s))}
    if eng.block is None:
        out["step"] = eng._step.lower(
            eng._w, eng._pool, eng._dev(eng._tables), eng._dev(eng._last),
            eng._toks, eng._dev(eng._pos))
    else:
        out["step"] = eng._step.lower(
            eng._w, eng._pool, *eng._block_args(np.arange(eng.num_slots)))
    if not eng.cache_spec.expert_layers:
        out["verify"] = eng._verify.lower(
            eng._w, eng._pool, eng._dev(eng._tables),
            eng._dev(np.zeros((eng.num_slots, 3), np.int32)),
            eng._dev(eng._pos))
    return out


def test_compiled_program_names_are_the_benchmarks(tiny):
    """The block pass is the engine's decode program: ``jit__step_fn``;
    the prefill and the scatter keep their names too."""
    eng = _server(tiny[0]).engine
    lowered = _lowered(eng)
    _first, rows = eng.prefill_rows(np.ones((1, 8), np.int32),
                                    np.full(1, 6, np.int32))
    lowered["scatter"] = eng._scatter.lower(
        eng._pool, rows, eng._dev(np.full(2, eng.num_blocks, np.int32)))
    programs = _bench_module("families", "sdar.py").Cell.programs
    for key, low in lowered.items():
        name = re.search(r"module @(\S+)", low.as_text()).group(1)
        assert re.search(programs[key], name), (key, name)
    assert "block_commit" in lowered["step"].as_text(debug_info=True)


#: sha256 (16 hex digits) of the lowered text of the tiny Llama's and the
#: tiny LFM2's served programs on the commit before the block decoder
#: (PR 29, fe17b9d): the window's, the mask's and the kernel's new
#: static flags leave the default path's programs letter for letter.
#: A PR that changes those programs on purpose reads the new values off
#: this test's failure.  PR 39 did, for the two ``step`` programs alone
#: (they were 0d534b8b0dc2a49b and cb6ce8a7aabce20f): the step takes the
#: output of the step before it as one more argument and reads a slot's
#: token from it where the host wrote none (``_carried``), so that the
#: next step can be queued before this one's tokens are fetched.
PARENT_PROGRAMS = {
    "llama": {"step": "27e214ef9335e8e9", "prefill": "b61a136db24e5190",
              "verify": "d321f3b3237cf784"},
    "lfm2": {"step": "aa9bcd45d31ee381", "prefill": "a5ad11b502cdbab8"},
}


@pytest.mark.parametrize("model", ["llama", "lfm2"])
def test_other_models_programs_lower_as_before_the_block_decoder(model):
    if model == "llama":
        from mxnet_tpu.models.llama import llama_tiny as make
    else:
        from mxnet_tpu.models.lfm2 import lfm2_moe_tiny as make
    net = make()
    net.initialize()
    got = {k: hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
           for k, low in _lowered(_server(net).engine).items()}
    assert got == PARENT_PROGRAMS[model]


# --- the benchmark's files -------------------------------------------------------

def test_block_step_bytes_total_to_the_issues_table():
    fb = _bench_module("flops_bytes", "sdar_block_step.py")
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "sdar_30b_a3b_l6.json")))
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    expert = 3 * 2048 * 768
    assert fb.expert_bytes(cfg) == 2 * expert == 9_437_184
    layer = attn + 2 * 2048 + 128 * 2048
    fixed = 2 * 151936 * 2048 + 2048 + 6 * layer
    assert fb.fixed_weight_bytes(cfg) == 2 * fixed
    total = fb.fixed_weight_bytes(cfg) + 6 * 128 * fb.expert_bytes(cfg)
    assert fb.weight_bytes(cfg) == total
    assert total / 2 / 1e6 == pytest.approx(4361, abs=1)     # parameters
    assert total / 1e9 == pytest.approx(8.72, abs=0.01)
    assert (layer + 128 * expert) / 1e6 == pytest.approx(623.1, abs=0.1)
    assert fb.kv_bytes_per_token(cfg) == 12 * 1024            # 12 KiB
    # a pass: the weights outside the embedding table, the touched
    # experts once, the rows' embeddings, K/V read to each block's end
    # plus the block's own rows written
    need = fb.bytes_needed(cfg, active_slots=100, kv_tokens=30_000,
                           experts_touched=700)
    assert need == (fb.fixed_weight_bytes(cfg) - 2 * 151936 * 2048
                    + 700 * fb.expert_bytes(cfg) + 400 * 2048 * 2
                    + (30_000 + 400) * 12 * 1024)
    assert need < fb.weight_bytes(cfg)
    # operations: a row's projections, router, 8 experts and the head,
    # and the attention over what the block's rows see
    per_row = 6 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 128 * 2048
                   + 8 * expert) + 151936 * 2048
    flops = fb.flops_needed(cfg, active_slots=100, kv_tokens=30_000)
    assert flops == 2 * (400 * per_row + 6 * 2 * 32 * 128 * 30_000 * 4)
    # at a full pass the least time is the bytes', not the operations'
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
    full = fb.bytes_needed(cfg, 128, 60_000, 768)
    assert full / peaks["hbm_bytes_per_s"] \
        > fb.flops_needed(cfg, 128, 60_000) / peaks["bf16_flops_per_s"]


def test_benchmark_config_keeps_every_published_width():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "sdar_30b_a3b_l6.json")))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        for line in open(catalog):
            if json.loads(line)["name"] == "SDAR-30B-A3B-Chat":
                row = json.loads(line)
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["source"] == row["source_url"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == "sdar_30b_a3b_l6"][0]
    assert set(entry["reduced"]) == changed and entry["source"] == cfg["source"]
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"]["num_hidden_layers"] == 48
    assert {"block_length", "denoising_steps", "confidence_threshold",
            "mask_token_id"} <= set(cfg["assumed"])
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["confidence_threshold"], cfg["mask_token_id"]) \
        == (4, 4, 0.9, 151669)
    assert cfg["mask_token_id"] < cfg["vocab_size"] and "deployment" in cfg
    cell = [w for w in bench["workloads"]
            if w["name"] == "sdar_30b.chat_decode_sat"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("sdar_30b_a3b_l6", "chat_decode_sat_s128_bd", 1)
    mix = json.load(open(os.path.join(
        BENCH, "traffic", "chat_decode_sat_s128_bd.json")))
    twin = json.load(open(os.path.join(
        BENCH, "traffic", "chat_decode_sat_s128.json")))
    for key in ("driver", "clients", "prompt_tokens", "output_tokens",
                "distinct_sizes", "shared_prefix_tokens", "greedy", "system",
                "order_seed"):
        assert mix[key] == twin[key], key
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if "sdar_30b.chat_decode_sat"
              in m.get("workloads", ())}
    assert listed == {
        "out_tok_per_s", "tpot_p50_ms.sat", "decode_step_ms", "tick_host_ms",
        "device_idle_share.decode", "expert_rows_max_over_mean",
        "experts_touched_share", "passes_per_block", "block_slot_occupancy",
        "block_step_roofline",
        # the admission, from the lane log's slot.turn records (PR 34)
        "slot_turn_ms", "slot_wait_lane_ms", "handoff_wait_ms",
        "tick_stretch_ms", "ticks_behind_prefill_share",
        "free_slots_at_admit",
        # the host's side of a turn on two clocks (PR 49)
        "tick_offcpu_ms", "stall_share.gc", "stall_share.own",
        "stall_share.offcpu", "gc_pause_max_ms"}


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


DATA = os.path.join(BENCH, "tests", "data_sdar")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_on_the_cpu(harness, capsys, trace):
    """``chipbench/run.py`` end to end on ``chipbench/tests/data_sdar``: the
    new family, reference, traffic keys and readers at a tiny size."""
    res = harness.run(["--workload", "tiny_sdar.closed", "--seed", "4000000007",
                       "--seconds", "2", "--trace", str(trace),
                       "--control", "1"],
                      require_tpu=False, data_dir=DATA)
    compared = _compared(capsys.readouterr().out)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    chk = json.load(open(os.path.join(DATA, "traffic", "closed.json")))["check"]
    assert chk["gap_limit"] is None and "served_logit_gap_max" in compared
    assert compared["commits_not_the_output"] == 0
    assert compared["checked_tokens"] >= 60
    for row, key in (("served_logit_gap_mean", "gap_mean_limit"),
                     ("served_logit_gap_max_steady", "gap_steady_limit"),
                     ("served_logit_gap_share_over_0.05",
                      "gap_share_limit")):
        assert compared[row] <= chk[key] < compared["control." + row], row
    # the commit rule's own row: the program's passes commit what the
    # reference would, a wrong rule on the same passes would not
    limit = chk["commit_regret_mean_limit"]
    assert compared["commit_regret_mean"] <= limit \
        < compared["control.commit_regret_mean"]
    for rule in ("least_confident", "position_order"):
        assert compared["commit_regret_mean_if_" + rule] > 3 * limit, rule
    # the control came out as not correct, which the run requires
    assert compared["control.passes_every_limit"] == 0
    assert 0.5 < compared["steady_token_share"] <= 1.0
    assert compared["passes_reference_commits_otherwise"] <= 0.05
    if trace:
        # no TPU plane in a CPU trace: the trace readers return nothing;
        # the lane-log readers report
        assert {"expert_rows_max_over_mean", "experts_touched_share",
                "passes_per_block", "block_slot_occupancy", "tick_host_ms",
                "tpot_p50_ms.sat"} <= set(res["metrics"])
        assert "block_step_roofline" not in res["metrics"]
        assert 1.0 < res["metrics"]["passes_per_block"]["value"] <= 5.0
        assert 0 < res["metrics"]["block_slot_occupancy"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"out_tok_per_s", "setup_s"}


def _run_planted(harness, capsys, control=0):
    res = harness.run(["--workload", "tiny_sdar.closed", "--seed", "11",
                       "--seconds", "2", "--trace", "0",
                       "--control", str(control)],
                      require_tpu=False, data_dir=DATA)
    out = capsys.readouterr().out
    assert res["correct"] is False and res["failed"] == 0
    assert "served_logit_gap_mean" in out and "FAILED" in out
    return _compared(out)


GAP_ROWS = (("served_logit_gap_mean", "gap_mean_limit"),
            ("served_logit_gap_max_steady", "gap_steady_limit"),
            ("served_logit_gap_share_over_0.05", "gap_share_limit"))


def _check_limits():
    return json.load(open(os.path.join(DATA, "traffic",
                                       "closed.json")))["check"]


def test_a_causal_order_inside_a_block_is_not_correct(harness, capsys,
                                                      monkeypatch):
    """Planted: the decode pass's window keeps the verify's causal order,
    so a row does not see the rest of its block."""
    whole = pa.window
    # by name, whatever else the window's signature takes
    monkeypatch.setattr(
        pa, "window", lambda *a, **kw: whole(*a, **{**kw, "block": False}))
    compared = _run_planted(harness, capsys)
    assert compared["commits_not_the_output"] == 0


def test_a_block_whose_store_pass_is_skipped_is_not_correct(harness, capsys,
                                                            monkeypatch):
    """Planted, in the bookkeeping the pass runs on the device and the
    host runs a pass late: a block that has just lost its last mask is
    taken as stored, so what later blocks read of it is the K/V of its
    last denoising pass, which still saw a mask id."""
    whole = decoder.block_advance

    def skipping(xp, state, ids, commit, stepped, decoding):
        held, masked, step, pos0 = whole(xp, state, ids, commit, stepped,
                                         decoding)
        skip = stepped & state[1].any(axis=1) & ~masked.any(axis=1)
        return (xp.where(skip[:, None], decoding.mask_id, held),
                masked | skip[:, None], xp.where(skip, 0, step),
                xp.where(skip, pos0 + decoding.block_len, pos0))

    monkeypatch.setattr(decoder, "block_advance", skipping)
    compared = _run_planted(harness, capsys)
    assert compared["commits_not_the_output"] == 0


def test_a_wrong_commit_rule_is_not_correct(harness, capsys, monkeypatch):
    """Planted: the device commits the masked rows in position order, as
    a left-to-right decoder would, whatever their confidence.  Its tokens
    are still the best of the rows it made, so the three rows over the
    served logits pass; the commit rule's own row refuses it, at the
    reading the sound run shows for that rule."""
    def in_position_order(logits, ids, masked, step, decoding):
        x0 = jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
        first = masked & (jnp.cumsum(masked, axis=1) <= 1)
        return jnp.where(first, x0, ids), first

    monkeypatch.setattr(decoder, "block_commit", in_position_order)
    compared = _run_planted(harness, capsys)
    chk = _check_limits()
    assert compared["commits_not_the_output"] == 0
    for row, key in GAP_ROWS:
        assert compared[row] <= chk[key], row
    assert compared["commit_regret_mean"] > 3 * chk["commit_regret_mean_limit"]
    assert compared["commit_regret_mean"] == pytest.approx(
        compared["commit_regret_mean_if_position_order"])
    assert compared["passes_reference_commits_otherwise"] > 0.2


def test_a_control_that_passes_every_limit_is_not_correct(harness, capsys,
                                                          monkeypatch):
    """Planted: limits so wide that the float8 control passes them too.
    A ``--control 1`` run is correct only if the control is refused."""
    wide = dict(_check_limits(), gap_mean_limit=10.0, gap_steady_limit=10.0,
                gap_share_limit=1.0, commit_regret_mean_limit=10.0)
    load = harness.load_json

    def widened(*parts):
        got = load(*parts)
        if parts[-1] == "closed.json":
            got["check"] = wide
        return got

    monkeypatch.setattr(harness, "load_json", widened)
    compared = _run_planted(harness, capsys, control=1)
    assert compared["control.passes_every_limit"] == 1
    assert compared["commit_regret_mean"] == 0


@pytest.mark.parametrize("lc,masked,committed,need,want", [
    # the most confident of the masked rows went: the rule's own choice
    ([-1.0, -3.0, -2.0, -9.0], [1, 1, 1, 0], [1, 0, 0, 0], 1, 0.0),
    # the least confident went: by how much the best one left was ahead
    ([-1.0, -3.0, -2.0, -9.0], [1, 1, 1, 0], [0, 1, 0, 0], 1, 2.0),
    # two past the threshold (log 0.9 = -0.105), both committed
    ([-0.01, -0.05, -2.0, -3.0], [1, 1, 1, 1], [1, 1, 0, 0], 1, 0.0),
    # one of them left behind
    ([-0.01, -0.05, -2.0, -3.0], [1, 1, 1, 1], [1, 0, 0, 0], 1,
     -0.05 - np.log(0.9)),
    # a commit beyond the share that is not past the threshold
    ([-0.01, -0.5, -2.0, -3.0], [1, 1, 1, 1], [1, 1, 0, 0], 1,
     np.log(0.9) + 0.5),
    # the last masked row: no choice
    ([-4.0, -0.2, -0.3, -0.1], [1, 0, 0, 0], [1, 0, 0, 0], 1, 0.0),
])
def test_commit_regret(ref, lc, masked, committed, need, want):
    got = ref.commit_regret(np.asarray(lc), np.asarray(masked, bool),
                            np.asarray(committed, bool), need,
                            float(np.log(0.9)))
    assert got == pytest.approx(want, abs=1e-12)


def test_block_step_roofline_reads_its_familys_flops_bytes(harness,
                                                           monkeypatch):
    """The reader finds ``flops_bytes/<family>_block_step.py`` from the
    configuration, so a second block decoder brings a file, not an
    edit; at the cell's load the share is bytes-bound and under 100%."""
    from layer_metrics import block_step_roofline as reader
    import lane_spans
    import reduce_helpers

    cfg = json.load(open(os.path.join(
        BENCH, "configs", "sdar_30b_a3b_l6.json")))
    tick = {"block_len": 4, "n_active": 128, "kv_tokens": 128 * 400,
            "experts_touched": 6 * 127}
    monkeypatch.setattr(lane_spans, "records", lambda obs, kind: [tick])
    monkeypatch.setattr(reduce_helpers, "median_module_ms",
                        lambda obs, program: 28.0)
    obs = {"config": cfg, "peaks": {"hbm_bytes_per_s": 819e9,
                                    "bf16_flops_per_s": 197e12}}
    fb = _bench_module("flops_bytes", "sdar_block_step.py")
    want = fb.bytes_needed(cfg, 128, 128 * 400, 6 * 127) / 819e9 / 28e-3
    assert reader.read(obs) == pytest.approx(100 * want)
    assert 30 < reader.read(obs) < 100
    with pytest.raises(ModuleNotFoundError):
        reader.read(dict(obs, config=dict(cfg, family="llama")))
    assert reader.read(dict(obs, peaks=None)) is None

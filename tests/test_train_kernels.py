"""The trainer's kernels that a model with latent attention and routed
experts needs: the flash kernels with ``v`` narrower than ``q`` and ``k``
(forward, ``dq``, ``dkv``; causal, several tiles) against ``_sdpa_ref``'s
gradients, and the grouped expert feed-forward's backward in Pallas
interpret mode against the gradients XLA takes of the every-expert form."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import moe
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import grouped_ffn

rs = np.random.RandomState(7)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# --- flash attention at two head widths ------------------------------------------

@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,block", [(256, (128, 128)), (128, (128, 128)),
                                     (512, (128, 256)), (512, (256, 128))],
                         ids=["four_tiles", "one_tile", "wide_keys",
                              "tall_queries"])
def test_flash_kernels_with_a_narrower_v(causal, t, block):
    """q and k 24 wide, v 16: the Pallas forward (with its log-sum-exp), dq
    and dkv in interpret mode, at square tiles and at ``block_q`` and
    ``block_k`` apart both ways (the diagonal then crosses tiles off their
    corners, and a dead tile's step asks for another block than its own),
    and the chunked ``jax.numpy`` fall-backs."""
    b, h, d, dv = 2, 3, 24, 16
    q, k = (jnp.asarray(rs.randn(b, h, t, d), jnp.float32) for _ in "qk")
    v = jnp.asarray(rs.randn(b, h, t, dv), jnp.float32)
    do = jnp.asarray(rs.randn(b, h, t, dv), jnp.float32)
    scale = d ** -0.5
    want, vjp = jax.vjp(lambda q, k, v: fa._sdpa_ref(q, k, v, causal, scale),
                        q, k, v)
    grads = vjp(do)
    o, lse = fa._fa_forward_pallas(q, k, v, causal, scale, *block,
                                   with_lse=True, interpret=True)
    assert o.shape == (b, h, t, dv) and lse.shape == (b, h, t)
    _close(o, want, 2e-5)
    got = fa._fa_backward_pallas(q, k, v, o, do, lse, causal, scale, *block,
                                 interpret=True)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    for g, w in zip(got, grads):
        _close(g, w, 5e-5)
    # the fall-backs a CPU run takes
    _close(fa._fa_forward_chunked(q, k, v, causal, scale, block=block[1]),
           want, 2e-5)
    for g, w in zip(fa._fa_backward(q, k, v, want, do, causal, scale,
                                    block=block[1]), grads):
        _close(g, w, 5e-5)
    # and the entry, differentiated
    out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_raw(
        q, k, v, causal, scale), q, k, v)
    _close(out, want, 2e-5)
    for g, w in zip(vjp(do), grads):
        _close(g, w, 5e-5)


@pytest.mark.parametrize("t", [1024, 2048, 4096])
def test_the_rules_tiles_divide_the_sequence_and_fit(t):
    """``joyai_flash.pretrain_s4k``'s heads (192 / 128, bf16, causal) at its
    length and the two below it: each kernel's tiles divide the sequence, fit
    the VMEM a step may ask for, and are the ones the sweep found."""
    tiles = {kern: fa.train_blocks(kern, t, t, 192, 128, 2, True)
             for kern in ("fwd", "dq", "dkv")}
    for kern, (bq, bk) in tiles.items():
        assert t % bq == 0 and t % bk == 0
        assert fa.train_row_bytes(bq, bk, 192, 2, kern, 128) \
            <= fa.TRAIN_VMEM_BYTES
    # the backward under the mask goes wide only where four tiles of 1,024
    # span the sequence, and dkv's VMEM count refuses them at these heads
    assert tiles == {"fwd": (1024, 1024), "dkv": (512, 512),
                     "dq": (1024, 1024) if t == 4096 else (512, 512)}
    # heads of 256 at tiles of 1,024: Mosaic refuses dq and dkv, so must
    # the rule
    assert fa.train_blocks("dq", 4096, 4096, 256, 256, 2, True) == (512, 512)
    # no mask, no tile half masked: the backward goes wide at every length
    assert fa.train_blocks("dkv", t, t, 128, 128, 2, False) == (1024, 1024)


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
def test_a_causal_grid_past_one_tile_is_the_list_of_its_live_steps(bq, bk):
    """No step of the three kernels lies above the causal diagonal: their
    grid is ``_live_steps``' list, which holds every pair ``_tile_runs``
    and no other, in the order the kernel sweeps (k tiles inside for the
    forward and ``dq``, q tiles inside for ``dkv``), and the gauges count
    it; without the mask the grid is every pair."""
    t = 512
    nq, nk = t // bq, t // bk

    def runs(i, j):
        return bool(fa._tile_runs(i, j, None, block_q=bq, block_k=bk,
                                  causal=True))

    qs, ks = fa._live_steps(nq, nk, bq, bk)
    assert list(zip(qs, ks)) == [(i, j) for i in range(nq)
                                 for j in range(nk) if runs(i, j)]
    ks, qs = fa._live_steps(nq, nk, bq, bk, q_outside=False)
    assert list(zip(ks, qs)) == [(i, j) for i in range(nk)
                                 for j in range(nq) if runs(j, i)]
    live = len(qs)
    assert 0 < live < nq * nk

    q, k, v, do = (jnp.asarray(rs.randn(1, 1, t, 8), jnp.float32)
                   for _ in range(4))
    for causal, steps in ((True, live), (False, nq * nk)):
        telemetry.enable()
        try:
            o, lse = fa._fa_forward_pallas(q, k, v, causal, 0.5, bq, bk,
                                           with_lse=True, interpret=True)
            fa._fa_backward_pallas(q, k, v, o, do, lse, causal, 0.5, bq, bk,
                                   interpret=True)
            gauges = telemetry.gauges()
        finally:
            telemetry.disable()
        for kern in ("fwd", "dq", "dkv"):
            assert gauges[f"flash.grid_steps.{kern}"] == steps
            assert gauges[f"flash.live_steps.{kern}"] == steps
            assert gauges[f"flash.rows_per_step.{kern}"] == 1


#: sha256 (16 hex digits) of the jaxpr (source locations taken out) of the
#: differentiated ``flash_attention_raw`` with the Pallas kernels forced, on
#: the commit before ``v`` got a width of its own (PR 43, 5a061cd): at equal
#: widths the one-tile kernels are what they were, grid, blocks and bodies.
#: Past one tile PR 45 changed the program on purpose (tiles from
#: ``train_blocks``, the causal grid a list of its live steps, no guard on
#: ``lse``, the backward's statistics along lanes) and the second hash is
#: that program's.
PARENT_FLASH = {
    ((128, 12, 128, 64), False): "414ec3460b14916a",    # bert_base.pretrain_s128
    ((2, 8, 1024, 64), True): "37376d5ea75768da",       # past one tile, causal
}


@pytest.mark.parametrize("shape,causal", list(PARENT_FLASH))
def test_equal_widths_trace_the_kernels_of_the_parent(monkeypatch, shape,
                                                      causal):
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention_raw(q, k, v, causal, 0.125) \
            .astype(jnp.float32).sum()

    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_FLASH[shape, causal]
    assert fa.train_tiles(shape[0] * shape[1], shape[2], shape[2], shape[3]) \
        == (16 if shape[2] == 128 else 1)


def test_train_form_names_the_tiles(monkeypatch):
    assert fa.train_form((4, 32, 4096, 192), 128) == "chunked"   # a CPU
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    assert fa.train_form((4, 32, 4096, 192), 128, causal=True) \
        == "pallas:fwd1024x1024,dq1024x1024,dkv512x512:d192/128:hb1"
    assert fa.train_form((128, 12, 128, 64)) \
        == "pallas:fwd128x128,dq128x128,dkv128x128:d64/64:hb16"


# --- the grouped expert feed-forward's backward -------------------------------

def _case(n, h, i, e, first, held, k, skew=True, part_live=True):
    x = jnp.asarray(rs.randn(n, h), jnp.float32)
    bank = [jnp.asarray(rs.randn(*s) * 0.1, jnp.float32)
            for s in ((held, h, i), (held, h, i), (held, i, h))]
    logits = rs.randn(n, e)
    if skew:
        logits[:, first + 1] += 3.0          # most rows take this one
        logits[:, first + held - 2] -= 100.0    # and this one gets no row
    idx = jnp.asarray(np.argsort(-logits, axis=1)[:, :k].astype(np.int32))
    w = jnp.asarray(rs.rand(n, k), jnp.float32)
    live = jnp.asarray(rs.rand(n) > 0.1) if part_live else None
    return x, idx - first, w, bank, live


def _every_expert(x, idx, w, bank, live):
    """The other form of ``routed_ffn``, written out: what XLA differentiates."""
    n, held = x.shape[0], bank[0].shape[0]
    there = (idx >= 0) & (idx < held)
    if live is not None:
        there = there & live[:, None]
    comb = jnp.zeros((n, held + 1)).at[
        jnp.arange(n)[:, None], jnp.where(there, idx, held)].add(
            jnp.where(there, w, 0.0))[:, :held]
    g = jnp.einsum("nh,ehi->nei", x, bank[0])
    u = jnp.einsum("nh,ehi->nei", x, bank[1])
    act = g * jax.nn.sigmoid(g) * u * comb[:, :, None]
    return jnp.einsum("nei,eih->nh", act, bank[2])


def _sorted_pairs(idx, live, held):
    """The kernels' listing, in numpy: (expert, row) of every held pair,
    expert by expert and row by row."""
    there = np.asarray((idx >= 0) & (idx < held))
    if live is not None:
        there = there & np.asarray(live)[:, None]
    rows, cols = np.nonzero(there)
    experts = np.asarray(idx)[rows, cols]
    order = np.lexsort((rows, experts))
    return experts[order], rows[order]


@pytest.mark.parametrize("n,window,token_tile,dead", [
    (300, None, None, None), (300, 256, None, None), (129, 128, None, None),
    (300, 256, 64, None), (300, 256, 64, (64, 128)), (129, 128, 64, None),
    (300, 128, 128, (0, 128))],
    ids=["one_window_forward", "windows_of_256", "rows_off_the_tile",
         "token_tiles_of_64", "a_token_tile_without_a_pair",
         "rows_off_the_token_tile", "the_first_token_tile_dead"])
def test_grouped_backward_matches_every_expert(n, window, token_tile, dead):
    """Skewed routing, an expert with no row, rows not a multiple of the
    tile, a held part (6 of 16 from the 4th) of the router, rows no request
    owns: dX, the three banks' gradients and the combine weights'.  With
    token tiles: a row held by several experts inside one window and across
    two, an expert's group cut by a window's edge, the last window's unused
    rest, a token tile no pair falls in (``dead`` rows no request owns), a
    last token tile that is part rows."""
    x, idx, w, bank, live = _case(n, 128, 128, 16, 3, 6, 4)
    if dead:
        live = live & ~((jnp.arange(n) >= dead[0]) & (jnp.arange(n) < dead[1]))
    dy = jnp.asarray(rs.randn(n, 128), jnp.float32)

    def ker(x, w, *bank):
        return grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, live, window=window, token_tile=token_tile,
            interpret=True)

    def ref(x, w, *bank):
        return _every_expert(x, idx, w, bank, live)

    _close(ker(x, w, *bank), ref(x, w, *bank), 1e-5)
    got = jax.grad(lambda *a: (ker(*a) * dy).sum(), argnums=range(5))(
        x, w, *bank)
    want = jax.grad(lambda *a: (ref(*a) * dy).sum(), argnums=range(5))(
        x, w, *bank)
    for g, r in zip(got, want):
        _close(g, r, 2e-5)
    # the expert without a row: zeros, not what the buffer held
    empty = 6 - 2
    assert not np.asarray(got[2][empty]).any()
    assert not np.asarray(got[4][empty]).any()
    # a pair on another chip's expert, or of a row nobody owns, has no say
    there = np.asarray((idx >= 0) & (idx < 6) & live[:, None])
    assert not np.asarray(got[1])[~there].any()
    if token_tile and window:
        # what the case is there to meet, said of its own listing
        experts, rows = _sorted_pairs(idx, live, 6)
        wins = np.arange(len(rows)) // window
        assert len(rows) > window and len(rows) % window
        twice = [wins[rows == r] for r in np.unique(rows)
                 if (rows == r).sum() > 1]
        assert any(len(set(t)) < len(t) for t in twice)   # inside one window
        assert any(len(set(t)) > 1 for t in twice)        # across two
        assert any(len(set(wins[experts == e])) > 1 for e in range(6))
        tiles = set(range(-(-n // token_tile)))
        assert (tiles - set(rows // token_tile) != set()) == bool(dead)
        assert n % token_tile


#: the four served banks whose 512-row calls fit one window, cut down in
#: rows and to widths of a lane tile or two (the published widths run on
#: the chip, ``tests_tpu/test_grouped_ffn_tpu.py``, and compile for a v5e
#: in ``tests/test_paged_attention.py``): the router's experts, the part
#: held here, experts a row, the router's width, the experts' (a latent
#: layer's differ), an expert's width, the scores, the expert's kind
SERVED = {
    "sdar_30b": dict(e=128, held=(0, 128), k=8, wide=128, h=128, i=128,
                     score="softmax", kind="swiglu"),
    "lfm2_24b": dict(e=64, held=(0, 64), k=4, wide=128, h=128, i=256,
                     score="sigmoid", kind="swiglu"),
    "qwen3_next": dict(e=512, held=(128, 128), k=10, wide=128, h=128, i=128,
                       score="softmax", kind="swiglu"),
    "nemotron3_super": dict(e=512, held=(384, 128), k=22, wide=256, h=128,
                            i=256, score="sigmoid", kind="relu2"),
}


def _plain(x, idx, w, bank, live, kind):
    """A row at a time, a pair at a time, float64."""
    held = bank[1].shape[0]
    wg, wu, wd = (None if a is None else np.asarray(a, np.float64)
                  for a in bank)
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    for r in range(x.shape[0]):
        for e, we in zip(np.asarray(idx)[r], np.asarray(w, np.float64)[r]):
            if not live[r] or not 0 <= e < held:
                continue
            if kind == "relu2":
                act = np.maximum(x[r] @ wu[e], 0.0) ** 2
            else:
                g = x[r] @ wg[e]
                act = g / (1.0 + np.exp(-g)) * (x[r] @ wu[e])
            out[r] += we * (act @ wd[e])
    return out


@pytest.mark.parametrize("routing", [
    "even", "one_expert_on_every_row", "an_expert_without_a_row",
    "dead_rows", "pairs_off_the_row_tile"])
@pytest.mark.parametrize("bank", sorted(SERVED))
def test_the_resident_form_matches_every_expert_and_a_plain_sum(bank,
                                                                routing):
    """A call whose pairs fit one window (the rows and their float32 sum
    in VMEM, ``grouped_expert_ffn_resident``) against ``routed_ffn``'s
    ``every_expert`` form, which a CPU takes, and against a plain float64
    sum a pair at a time: 64 rows whose ``N x k`` pairs are whole row
    tiles, and 45 whose are not; an expert every row holds (its group
    runs over tiles), one nobody holds, rows no request owns."""
    b = SERVED[bank]
    n = 45 if routing == "pairs_off_the_row_tile" else 64
    first, held = b["held"]
    relu2 = b["kind"] == "relu2"
    x = jnp.asarray(rs.randn(n, b["wide"]), jnp.float32)
    lat = jnp.asarray(rs.randn(n, b["h"]), jnp.float32) \
        if b["h"] != b["wide"] else None
    rw = jnp.asarray(rs.randn(b["e"], b["wide"]) * 0.3, jnp.float32)
    wg, wu, wd = (jnp.asarray(rs.randn(*s) * 0.1, jnp.float32) for s in (
        (held, b["h"], b["i"]), (held, b["h"], b["i"]),
        (held, b["i"], b["h"])))
    wg = None if relu2 else wg
    bias = np.zeros(b["e"], np.float32)
    if routing == "one_expert_on_every_row":
        bias[first + 3] = 100.0
    if routing == "an_expert_without_a_row":
        bias[first + held - 2] = -100.0
    live = rs.rand(n) > 0.3 if routing == "dead_rows" else np.ones(n, bool)
    assert grouped_ffn.rows_form(n, b["k"], b["h"], b["i"], 4) == "resident"
    assert moe.expert_product(n, b["k"], held, b["h"], b["i"],
                              jnp.float32) == "every_expert"
    every, counts = moe.routed_ffn(
        x, rw, wg, wu, wd, b["k"], score=b["score"],
        choice_bias=jnp.asarray(bias), experts_held=b["held"],
        live=jnp.asarray(live), kind=b["kind"], rows=lat)
    idx, w = moe.route(x, rw, b["k"], b["score"], jnp.asarray(bias))
    rows = x if lat is None else lat
    got = grouped_ffn.grouped_expert_ffn(
        rows, idx - first, w, wg, wu, wd, jnp.asarray(live), interpret=True,
        kind=b["kind"])
    assert got.shape == rows.shape and got.dtype == rows.dtype
    # what the case is there to meet, said of its own routing
    here = np.asarray(counts)[first:first + held]
    assert (n * b["k"] % grouped_ffn.ROW_TILE != 0) \
        == (routing == "pairs_off_the_row_tile")
    assert (here[3] == n) == (routing == "one_expert_on_every_row")
    assert (here[held - 2] == 0) == (routing == "an_expert_without_a_row") \
        or bank != "sdar_30b"
    assert 0 < here.sum() <= live.sum() * b["k"]
    # (a row no request owns: zero here, anything there, nobody reads it)
    _close(np.asarray(got)[live], np.asarray(every)[live], 2e-5)
    _close(got, _plain(rows, np.asarray(idx) - first, w, (wg, wu, wd), live,
                       b["kind"]), 2e-5)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("sizes", [
    [3, 0, 8, 9, 17, 0, 1], [0, 0, 40], [0, 0, 0], [8, 8, 8, 8]],
    ids=["mixed", "one_long_group", "no_pair_held", "whole_tiles"])
def test_a_resident_visit_is_one_experts_pairs_wherever_they_lie(sizes):
    """``_own_visits``: every listed pair in exactly one visit, a visit
    one expert's and ``tm`` pairs at most, a group of up to ``tm`` pairs
    one visit however it lies across the list's row tiles (the windowed
    form's ``_visits`` gives it one for each tile it touches)."""
    tm, held = 8, len(sizes)
    m = sum(sizes)
    key = np.repeat(np.arange(held), sizes)
    key = np.concatenate([key, np.full(-(-(m + 5) // tm) * tm - m, held)])
    eid, first, hi, total = (np.asarray(a) for a in grouped_ffn._own_visits(
        jnp.asarray(key, jnp.int32), held, tm))
    total = int(total[0])
    assert total == sum(-(-s // tm) for s in sizes)
    assert total <= int(grouped_ffn._visits(
        jnp.asarray(key, jnp.int32), held, tm)[4][0])
    assert len(eid) == len(key) // tm + held
    pairs = [np.arange(first[v], hi[v]) for v in range(total)]
    assert all(0 < len(p) <= tm for p in pairs)
    assert all((key[p] == eid[v]).all() for v, p in enumerate(pairs))
    assert (np.concatenate(pairs or [np.arange(0)]) == np.arange(m)).all()
    # the static rest repeats the last visit: no new block is fetched
    assert (eid[total:] == eid[max(total - 1, 0)]).all()


def test_the_resident_form_in_bfloat16_sums_in_float32():
    """The rows wait in VMEM as float32 (a one-row slice of packed bf16
    is half a sublane) and are products' operands as bf16 again, exactly;
    the sum over a row's ``k`` stays float32 to the one cast."""
    b = SERVED["sdar_30b"]
    n, held = 64, 128
    x = jnp.asarray(rs.randn(n, b["h"]), jnp.bfloat16)
    bank = [jnp.asarray(rs.randn(*s) * 0.1, jnp.bfloat16) for s in (
        (held, b["h"], b["i"]), (held, b["h"], b["i"]), (held, b["i"], b["h"]))]
    idx = jnp.asarray(np.argsort(-rs.randn(n, held), axis=1)[:, :8]
                      .astype(np.int32))
    w = jnp.asarray(rs.rand(n, 8), jnp.float32)
    got = grouped_ffn.grouped_expert_ffn(x, idx, w, *bank, interpret=True)
    assert got.dtype == jnp.bfloat16
    # float64 from the same bf16 values: the kernel rounds its
    # activations to bf16 once and its result once
    want = _plain(x.astype(jnp.float32), idx, w,
                  [a.astype(jnp.float32) for a in bank], np.ones(n, bool),
                  "swiglu")
    _close(got.astype(jnp.float32), want, 2.0 ** -6)


@pytest.mark.parametrize("rows,k,h,i,want", [
    (512, 8, 2048, 768, "resident"), (512, 4, 2048, 1536, "resident"),
    (512, 10, 2048, 512, "resident"), (512, 22, 1024, 2688, "resident"),
    (2048, 8, 2048, 768, "resident"),     # 16,384 pairs: the last window
    (2304, 8, 2048, 768, "kernel"),       # the pairs exceed a window
    (16384, 8, 2048, 768, "kernel"), (32768, 8, 6144, 2048, "kernel"),
    # one window, but an expert walked in width tiles: the tiles fill the cap
    (512, 8, 6144, 2048, "kernel"), (512, 2, 4096, 14336, "kernel"),
], ids=["sdar_30b", "lfm2_24b", "qwen3_next", "nemotron3_super",
        "a_full_window", "past_a_window", "joyai_flash_trained",
        "glm5_long_prefill", "glm5_512_rows", "mixtral_512_rows"])
def test_the_rule_of_shapes_picks_where_the_rows_cross(rows, k, h, i, want):
    """``rows_form``: in VMEM where every pair fits one window and the
    rows, their float32 sum and the kernel's blocks fit the cap, else a
    window at a time through ``grouped_expert_ffn_rows``."""
    assert grouped_ffn.rows_form(rows, k, h, i) == want
    tm, wt = grouped_ffn.tiles(h, i)
    one = -(-rows * k // tm) * tm <= grouped_ffn.window_pairs(rows, k, h, tm)
    fits = grouped_ffn._resident_vmem_bytes(rows, h, wt, 2, tm, wt < i) \
        <= grouped_ffn._VMEM_CAP
    assert (want == "resident") == (one and fits)


#: sha256 (16 hex digits) of the jaxprs (source locations taken out) of the
#: windowed form on the commit before the resident form (PR 47, 8efc484):
#: the trained layer's forward and its gradients, the long prefill's
#: forward.  Their programs are the parent's to the letter.
PARENT_WINDOWED = {
    "joyai_flash_trained": ((16384, 8, 16, 2048, 768), "b6c120d36cdf064e"),
    "joyai_flash_trained_grad": ((16384, 8, 16, 2048, 768),
                                 "2259e0de4f2df175"),
    "glm5_long_prefill": ((32768, 8, 16, 6144, 2048), "08a97c9d88b6cdfc"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_WINDOWED))
def test_the_windowed_calls_are_the_parents_programs(cell):
    """The calls whose pairs exceed a window (``joyai_flash.pretrain_s4k``,
    ``glm5.longdoc_prefill``) are not this PR's."""
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    (rows, k, held, h, i), want = PARENT_WINDOWED[cell]
    avals = (sds((rows, h), bf), sds((rows, k), jnp.int32),
             sds((rows, k), jnp.float32), sds((held, h, i), bf),
             sds((held, h, i), bf), sds((held, i, h), bf))
    fn = grouped_ffn.grouped_expert_ffn
    if cell.endswith("_grad"):
        fn = jax.grad(lambda *a: grouped_ffn.grouped_expert_ffn(*a)
                      .astype(jnp.float32).sum(), argnums=(0, 2, 3, 4, 5))
    else:
        avals += (sds((rows,), jnp.bool_),)
    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(fn)(*avals))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert "grouped_expert_ffn_rows" in text
    assert "grouped_expert_ffn_resident" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("rows,k,held,h,i,want", [
    (16384, 8, 16, 2048, 768,
     dict(fwd=16384, form="kernel", token_tile=1024, row_tile=128)),
    (32768, 8, 16, 6144, 2048,
     dict(fwd=5376, form="kernel", token_tile=512, row_tile=None)),
    (512, 8, 128, 2048, 768,
     dict(fwd=4096, form="resident", token_tile=512, row_tile=128)),
], ids=["joyai_flash_trained", "glm5_long_prefill", "sdar_block_pass"])
def test_the_gauges_say_what_the_shapes_chose(rows, k, held, h, i, want):
    """Where the program is traced: the forward's and the backward's
    window, whether the rows cross in ``grouped_expert_ffn_rows`` or in
    VMEM inside the one kernel, and the token tile, at the three sparse
    cells' shapes."""
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    avals = (sds((rows, h), bf), sds((rows, k), jnp.int32),
             sds((rows, k), jnp.float32), sds((held, h, i), bf),
             sds((held, h, i), bf), sds((held, i, h), bf))

    def loss(x, idx, w, *bank):
        return grouped_ffn.grouped_expert_ffn(x, idx, w, *bank) \
            .astype(jnp.float32).sum()

    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        for name in ("fwd.window_pairs", "rows_form", "token_tile",
                     "bwd.row_tile", "bwd.window_pairs"):
            telemetry._gauges.pop("grouped_ffn." + name, None)
        fn = jax.grad(loss, argnums=(0, 2)) if want["row_tile"] else loss
        jax.make_jaxpr(fn)(*avals)
        got = telemetry.gauges()
    finally:
        if not was:
            telemetry.disable()
    assert got["grouped_ffn.fwd.window_pairs"] == want["fwd"]
    assert got["grouped_ffn.rows_form"] == want["form"]
    # (a resident forward sets none; its backward lists the held pairs)
    assert got.get("grouped_ffn.token_tile") == want["token_tile"]
    if want["row_tile"]:
        assert got["grouped_ffn.bwd.row_tile"] == want["row_tile"]
        assert got["grouped_ffn.bwd.window_pairs"] == want["fwd"]


def test_grouped_backward_in_bfloat16_accumulates_in_float32():
    x, idx, w, bank, live = _case(256, 128, 128, 8, 0, 8, 2, skew=False,
                                  part_live=False)
    dy = jnp.asarray(rs.randn(256, 128), jnp.float32)
    low = [a.astype(jnp.bfloat16) for a in (x, *bank)]

    def ker(x, *bank):
        return grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, interpret=True).astype(jnp.float32)

    def ref(x, *bank):
        return _every_expert(x, idx, w, bank, None)

    got = jax.grad(lambda *a: (ker(*a) * dy).sum(), argnums=range(4))(*low)
    want = jax.grad(lambda *a: (ref(*a) * dy).sum(), argnums=range(4))(
        *(a.astype(jnp.float32) for a in low))
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16
        _close(g.astype(jnp.float32), r, 3e-2)


def test_a_walked_expert_has_no_backward_yet():
    x, idx, w, bank, live = _case(256, 128, 256, 8, 0, 8, 2)
    with pytest.raises(NotImplementedError, match="width tiles"):
        jax.grad(lambda x: grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, width_tile=128, interpret=True).sum())(x)


def test_routed_ffn_differentiates_in_the_form_expert_product_names():
    """On a CPU ``expert_product`` says ``every_expert`` and XLA takes the
    gradient; the router learns through the combine weights."""
    n, h, i, e = 64, 16, 8, 8
    x = jnp.asarray(rs.randn(n, h), jnp.float32)
    router = jnp.asarray(rs.randn(e, h), jnp.float32)
    bank = [jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
            for s in ((4, h, i), (4, h, i), (4, i, h))]
    assert moe.expert_product(n, 2, 4, h, i, jnp.float32) == "every_expert"
    g = jax.grad(lambda r: moe.routed_ffn(
        x, r, *bank, 2, score="sigmoid", experts_held=(2, 4))[0].sum())(router)
    assert np.abs(np.asarray(g)).max() > 0

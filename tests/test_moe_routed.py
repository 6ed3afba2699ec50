"""The dropless routed feed-forward (``models.moe.route`` / ``routed_ffn``)
against a row-by-row numpy oracle: nothing dropped, the choice by score plus
bias and the weights by score alone, the shares of ``experts_held`` adding up
to the uncut layer, and the row counts leaving out rows no request owns.
Every case runs in both of ``routed_ffn``'s forms: every held expert on every
row, and ``ops.grouped_ffn`` (the kernel under the Pallas interpreter, steered
to it as a chip's rule would at hundreds of rows); then the kernel's own
shapes (an expert walked in width tiles, the held pairs in windows), the rule
on shapes alone, and the ``expert_product`` counter of a served model.

(Beside ``tests/test_moe.py``, whose module is in the slow lane: these run in
tier 1.)
"""
import functools
import time

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import moe
from mxnet_tpu.ops import grouped_ffn

N, H, I, E = 24, 16, 12, 8
#: the kernel's row tile here: 24 rows x 1, 3 or 4 experts are 24, 72 or
#: 96 pairs, 16 of them a tile, so the last tile is part padding, most
#: tiles straddle experts and a large group spans several
TILE = 16


def _interpreted(patch, row_tile=TILE, width_tile=None, window=None,
                 token_tile=None):
    """The kernel under the interpreter (its jits trace once a shape).
    ``width_tile`` None: the whole width; ``window`` None: every pair in
    one (the shapes here say so); ``token_tile`` None: every row in one."""
    patch.setattr(grouped_ffn, "grouped_expert_ffn", functools.partial(
        grouped_ffn.grouped_expert_ffn, row_tile=row_tile,
        width_tile=width_tile, window=window, token_tile=token_tile,
        interpret=True))


@pytest.fixture(params=["every_expert", "grouped_kernel"])
def form(request, monkeypatch):
    """Both forms of ``routed_ffn``, the rule answered for it."""
    monkeypatch.setattr(moe, "expert_product", lambda *a: request.param)
    _interpreted(monkeypatch)
    return request.param


def _bank(seed=0, e=E):
    rs = np.random.RandomState(seed)
    f = lambda *s: (rs.randn(*s) * 0.3).astype(np.float32)  # noqa: E731
    return dict(x=f(N, H), rw=f(e, H), wg=f(e, H, I), wu=f(e, H, I),
                wd=f(e, I, H), b=(rs.randn(e) * 0.5).astype(np.float32))


def _oracle(t, k, score, bias, renormalize, scale, held=None, kind="swiglu"):
    """Row by row: scores over all experts, the top k of score + bias, the
    weights from the scores alone, the chosen experts' SwiGLUs (or, ``kind``
    ``"relu2"``, their two-matrix squared ReLUs) summed."""
    logits = t["x"] @ t["rw"].T
    if score == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
    else:
        ex = np.exp(logits - logits.max(-1, keepdims=True))
        s = ex / ex.sum(-1, keepdims=True)
    pick = s + (t["b"] if bias else 0.0)
    y = np.zeros((len(t["x"]), H), np.float32)
    counts = np.zeros(len(t["rw"]), np.int64)
    for n in range(len(t["x"])):
        idx = np.argsort(-pick[n], kind="stable")[:k]
        w = s[n, idx]
        if renormalize:
            w = w / (w.sum() + 1e-6)
        for e, we in zip(idx, w * scale):
            counts[e] += 1
            if held is not None and not held[0] <= e < held[0] + held[1]:
                continue
            u = t["x"][n] @ t["wu"][e]
            if kind == "relu2":
                act = np.square(np.maximum(u, 0.0))
            else:
                g = t["x"][n] @ t["wg"][e]
                act = g / (1.0 + np.exp(-g)) * u
            y[n] += we * (act @ t["wd"][e])
    return y, counts


def _run(t, k, score="sigmoid", bias=True, renormalize=True, scale=1.0,
         held=None, live=None, kind="swiglu"):
    sl = slice(None) if held is None else slice(held[0], held[0] + held[1])
    y, c = moe.routed_ffn(
        jnp.asarray(t["x"]), jnp.asarray(t["rw"]),
        None if kind == "relu2" else jnp.asarray(t["wg"][sl]),
        jnp.asarray(t["wu"][sl]), jnp.asarray(t["wd"][sl]), k, score=score,
        choice_bias=jnp.asarray(t["b"]) if bias else None,
        renormalize=renormalize, scale=scale, experts_held=held,
        live=None if live is None else jnp.asarray(live), kind=kind)
    return np.asarray(y), np.asarray(c)


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("score,bias,renormalize,scale,kind", [
    ("sigmoid", True, True, 1.0, "swiglu"),      # the LFM2 router
    ("sigmoid", False, False, 2.5, "swiglu"),    # a scale on raw scores
    ("softmax", False, True, 1.0, "swiglu"),     # the Mixtral router
    ("softmax", True, False, 1.0, "swiglu"),
    ("sigmoid", True, True, 5.0, "relu2"),       # Nemotron-H's experts
    ("softmax", False, True, 1.0, "relu2"),
])
def test_routed_ffn_equals_the_row_by_row_oracle(form, k, score, bias,
                                                 renormalize, scale, kind):
    t = _bank(1)
    got, counts = _run(t, k, score, bias, renormalize, scale, kind=kind)
    want, wc = _oracle(t, k, score, bias, renormalize, scale, kind=kind)
    assert np.abs(got - want).max() < 1e-5
    assert (counts == wc).all() and counts.sum() == N * k


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_nothing_is_dropped_when_the_bias_sends_every_token_to_four_experts(
        form, score):
    """A fixed-capacity layer would drop most of these rows: 24 tokens x 4
    all on the same four of eight experts (and four experts without a row:
    the kernel never visits them)."""
    t = _bank(2)
    t["b"] = np.where(np.arange(E) % 2 == 0, 100.0, 0.0).astype(np.float32)
    got, counts = _run(t, 4, score)
    assert (counts == np.where(np.arange(E) % 2 == 0, N, 0)).all()
    want, _ = _oracle(t, 4, score, True, True, 1.0)
    assert np.abs(got - want).max() < 1e-5
    assert (np.abs(got).sum(-1) > 0).all()      # every row got its experts


def test_the_choice_uses_score_plus_bias_and_the_weights_the_score():
    """One row, scores fixed by the router: the bias lifts the two weakest
    experts over the rest, and their weights are still their (small) scores
    renormalised, not score + bias."""
    x = np.zeros((1, H), np.float32)
    x[0, 0] = 1.0
    rw = np.zeros((E, H), np.float32)
    rw[:, 0] = np.linspace(2.0, -2.0, E)        # expert 0 scores highest
    bias = np.zeros(E, np.float32)
    bias[[6, 7]] = 10.0
    idx, w = moe.route(jnp.asarray(x), jnp.asarray(rw), 2, "sigmoid",
                       jnp.asarray(bias), renormalize=True)
    assert sorted(np.asarray(idx)[0].tolist()) == [6, 7]
    s = 1.0 / (1.0 + np.exp(-rw[:, 0]))
    order = np.asarray(idx)[0]
    want = s[order] / (s[order].sum() + 1e-6)
    assert np.allclose(np.asarray(w)[0], want, atol=1e-6)
    # without the bias the same row goes to the two strongest
    idx0, _ = moe.route(jnp.asarray(x), jnp.asarray(rw), 2, "sigmoid")
    assert sorted(np.asarray(idx0)[0].tolist()) == [0, 1]


@pytest.mark.parametrize("k,score,bias,experts,kind", [
    (2, "sigmoid", True, 8, "swiglu"), (3, "sigmoid", True, 8, "swiglu"),
    (8, "softmax", False, 16, "swiglu"),    # the Qwen3-MoE router's shape
    (6, "sigmoid", True, 16, "relu2"),      # two-matrix experts
], ids=["2", "3", "8_of_16_softmax", "6_of_16_relu2"])
@pytest.mark.parametrize("share", [1, 2, 4])
def test_the_shares_of_experts_held_add_up_to_the_uncut_layer(
        form, k, score, bias, experts, kind, share):
    """Eight (or sixteen) experts over chips that hold 1, 2 or 4 each:
    every share routes over all experts and computes its own experts'
    part; the parts add up to the whole layer, and each agrees with the
    oracle restricted to its range."""
    t = _bank(3, experts)
    whole, counts = _run(t, k, score, bias, kind=kind)
    total = np.zeros_like(whole)
    for first in range(0, experts, share):
        part, c = _run(t, k, score, bias, held=(first, share), kind=kind)
        want, _ = _oracle(t, k, score, bias, True, 1.0,
                          held=(first, share), kind=kind)
        assert np.abs(part - want).max() < 1e-5
        assert (c == counts).all()              # routing is over all experts
        total += part
    assert np.abs(total - whole).max() < 1e-5


@pytest.mark.parametrize("owned", ["none", "every_other", "first_five",
                                   "all"])
def test_rows_no_request_owns_are_computed_and_not_counted(form, owned):
    """A served program also routes vacant slots and the padded end of a
    prompt: ``live`` keeps them out of the counts and changes no row a
    request owns; the others come back as they would have or zero (the
    kernel leaves them out like another chip's pairs)."""
    t = _bank(4)
    live = {"none": np.zeros(N, bool), "every_other": np.arange(N) % 2 == 0,
            "first_five": np.arange(N) < 5, "all": np.ones(N, bool)}[owned]
    got, counts = _run(t, 3, live=live)
    every, _ = _run(t, 3)
    assert (got[live] == every[live]).all()
    if form == "grouped_kernel":
        assert not got[~live].any()
    else:
        assert (got == every).all()
    t["x"] = t["x"][live]
    _, want = _oracle(t, 3, "sigmoid", True, True, 1.0)
    assert (counts == want).all() and counts.sum() == 3 * live.sum()


@pytest.mark.parametrize("tile", [8, 16, 128])
def test_every_row_on_one_expert_and_the_forms_count_alike(monkeypatch, tile):
    """One group as long as the call (24 pairs: three tiles of 8, one and a
    half of 16, a fifth of 128), seven experts that no visit fetches; the
    counts are the one form's to the row."""
    t = _bank(6)
    t["b"] = np.where(np.arange(E) == 5, 100.0, 0.0).astype(np.float32)
    want, wc = _oracle(t, 1, "sigmoid", True, True, 1.0)
    _interpreted(monkeypatch, tile)
    got = {}
    for name in ("every_expert", "grouped_kernel"):
        monkeypatch.setattr(moe, "expert_product", lambda *a, n=name: n)
        got[name] = _run(t, 1)
        assert np.abs(got[name][0] - want).max() < 1e-5
    assert (got["grouped_kernel"][1] == got["every_expert"][1]).all()
    assert (wc == np.where(np.arange(E) == 5, N, 0)).all()


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("tile", [8, 16, 128])
@pytest.mark.parametrize("width_tiles", [2, 4])
def test_an_expert_walked_in_width_tiles_equals_the_oracle(monkeypatch, tile,
                                                           width_tiles, kind):
    """SwiGLU separates over the intermediate width: 12 wide in 2 tiles
    of 6 or 4 of 3, the float32 sum over them kept beside the visit and
    weight, mask and output applied once at the last; groups that
    straddle row tiles of 8 and 16, and one tile of 128 for all."""
    monkeypatch.setattr(moe, "expert_product", lambda *a: "grouped_kernel")
    _interpreted(monkeypatch, tile, I // width_tiles)
    t = _bank(7)
    got, counts = _run(t, 3, kind=kind)
    want, wc = _oracle(t, 3, "sigmoid", True, True, 1.0, kind=kind)
    assert np.abs(got - want).max() < 1e-5
    assert (counts == wc).all()
    part, _ = _run(t, 3, held=(2, 4), kind=kind)
    want, _ = _oracle(t, 3, "sigmoid", True, True, 1.0, held=(2, 4),
                      kind=kind)
    assert np.abs(part - want).max() < 1e-5


#: a chip that holds experts 6 and 7 of a router of 32
PART = (6, 2)


@pytest.mark.parametrize("case", ["several_windows", "no_pair_held",
                                  "every_row_on_one_held_expert",
                                  "dead_tail", "several_windows_relu2"])
@pytest.mark.parametrize("width_tiles", [1, 2])
@pytest.mark.parametrize("token_tile", [None, 8],
                         ids=["one_token_tile", "token_tiles_of_8"])
def test_the_held_pairs_in_windows_over_a_part_of_the_router(
        monkeypatch, case, width_tiles, token_tile):
    """24 rows x 4 of 32 experts, 2 of them held: the kernel gathers,
    visits and writes the held prefix of the sorted list alone, 16 sorted
    pairs (two row tiles) a window, as many windows as the held pairs
    take: several where a bias sends most rows here, none where it sends
    no row here (zeros), every one where all rows choose one held expert
    (k = 1: every pair held, the worst case, nothing dropped); and the
    padded end of a bucket (``live`` false) is left out like another
    chip's pairs: zeros there, the counts the live rows' alone.  The
    windows' rows go back into the rows' order through
    ``grouped_expert_ffn_rows``, the 24 rows in one token tile or in
    three of 8 (the dead tail's last tile has no pair and is never
    opened)."""
    monkeypatch.setattr(moe, "expert_product", lambda *a: "grouped_kernel")
    kind = "relu2" if case.endswith("relu2") else "swiglu"
    case = case.removesuffix("_relu2")
    t = _bank(8, 32)
    first, count = PART
    here = (np.arange(32) >= first) & (np.arange(32) < first + count)
    k, live = 4, None
    if case == "no_pair_held":
        t["b"] = np.where(here, -100.0, t["b"]).astype(np.float32)
    elif case == "every_row_on_one_held_expert":
        k = 1
        t["b"] = np.where(np.arange(32) == first, 100.0, 0.0) \
            .astype(np.float32)
    else:
        t["b"] = np.where(here, 1.0, t["b"]).astype(np.float32)
    if case == "dead_tail":
        live = np.arange(N) < 15
    window = 8 if k == 1 else 16
    _interpreted(monkeypatch, 8, I // width_tiles, window, token_tile)
    got, counts = _run(t, k, held=PART, live=live, kind=kind)
    want, wc = _oracle(t, k, "sigmoid", True, True, 1.0, held=PART, kind=kind)
    owned = np.ones(N, bool) if live is None else live
    held_pairs = int(_oracle(dict(t, x=t["x"][owned]), k, "sigmoid", True,
                             True, 1.0)[1][here].sum())
    assert {"several_windows": held_pairs > 2 * window,
            "no_pair_held": held_pairs == 0,
            "every_row_on_one_held_expert": held_pairs == N == 3 * window,
            "dead_tail": held_pairs > window}[case]
    assert np.abs(got[owned] - want[owned]).max() < 1e-5
    assert not got[~owned].any()
    if case == "no_pair_held":
        assert not got.any()
    if live is None:
        assert (counts == wc).all() and counts.sum() == N * k
    else:
        assert counts.sum() == k * live.sum()
        assert (counts == _oracle(dict(t, x=t["x"][live]), k, "sigmoid",
                                  True, True, 1.0)[1]).all()
    # one window for all the pairs: the same rows to rounding
    _interpreted(monkeypatch, 8, I // width_tiles)
    whole, _ = _run(t, k, held=PART, live=live, kind=kind)
    assert np.abs(got - whole).max() < 1e-6


def test_a_walked_experts_windows_go_through_the_combine():
    """Rows of 384 (three lane tiles), experts of 256 walked in two width
    tiles of 128, 40 rows x 2 of 3 held experts in windows of 16 pairs and
    token tiles of 16: the float32 sum over the width tiles leaves the
    forward kernel a pair's row, and ``grouped_expert_ffn_rows`` adds it
    to its token's."""
    rs = np.random.RandomState(11)
    n, h, i, held, k = 40, 384, 256, 3, 2
    x = (rs.randn(n, h) * 0.3).astype(np.float32)
    bank = [(rs.randn(*s) * 0.1).astype(np.float32)
            for s in ((held, h, i), (held, h, i), (held, i, h))]
    idx = np.argsort(rs.rand(n, held + 2), axis=1)[:, :k].astype(np.int32)
    w = rs.rand(n, k).astype(np.float32)
    got = np.asarray(grouped_ffn.grouped_expert_ffn(
        jnp.asarray(x), jnp.asarray(idx), jnp.asarray(w),
        *(jnp.asarray(b) for b in bank), row_tile=8, width_tile=128,
        window=16, token_tile=16, interpret=True))
    want = np.zeros((n, h), np.float32)
    pairs = 0
    for r in range(n):
        for e, we in zip(idx[r], w[r]):
            if e < held:
                g, u = x[r] @ bank[0][e], x[r] @ bank[1][e]
                want[r] += we * ((g / (1.0 + np.exp(-g)) * u) @ bank[2][e])
                pairs += 1
    assert pairs > 2 * 16 and pairs % 16
    assert np.abs(got - want).max() < 1e-4


def test_the_walk_over_a_window_of_the_sorted_list():
    """``_visits`` over a window that begins inside expert 1's group and
    ends among the pairs nobody holds (ids 4 and, for a dead row's or the
    padding's, more): expert 1 in tiles 0-1, expert 3 in tiles 1-2, no
    visit for experts 0 and 2 or for the tail."""
    key = jnp.asarray([1] * 11 + [3] * 8 + [4] * 9 + [5] * 4, jnp.int32)
    eid, tid, lo, hi, total = (np.asarray(a) for a in
                               grouped_ffn._visits(key, 4, 8))
    assert total.tolist() == [4] and len(eid) == 32 // 8 + 4 - 1
    assert eid.tolist() == [1, 1, 3, 3, 3, 3, 3]
    assert tid.tolist() == [0, 1, 1, 2, 2, 2, 2]
    assert lo.tolist() == [0, 0, 11, 11, 11, 11, 11]
    assert hi.tolist() == [11, 11, 19, 19, 19, 19, 19]


def test_the_kernels_walk_visits_each_touched_expert_once_in_order():
    """``_visits`` over sorted keys: groups of 5, 0, 20 and 7 pairs in tiles
    of 8 (the fourth id is the pairs nobody holds): expert 0 in tile 0,
    expert 2 in tiles 0-3, expert 3 in tile 3; the static rest repeats
    the last visit, so its blocks are fetched by no further step."""
    key = jnp.asarray([0] * 5 + [2] * 20 + [3] * 7 + [4] * 8, jnp.int32)
    eid, tid, lo, hi, total = (np.asarray(a) for a in
                               grouped_ffn._visits(key, 4, 8))
    assert total.tolist() == [6] and len(eid) == 40 // 8 + 4 - 1
    assert eid.tolist() == [0, 2, 2, 2, 2, 3, 3, 3]
    assert tid.tolist() == [0, 0, 1, 2, 3, 3, 3, 3]
    assert lo.tolist() == [0, 5, 5, 5, 5, 25, 25, 25]
    assert hi.tolist() == [5, 25, 25, 25, 25, 32, 32, 32]
    # no pair held at all: no visit, indices that exist
    none = grouped_ffn._visits(jnp.full((16,), 4, jnp.int32), 4, 8)
    assert int(none[4][0]) == 0
    assert 0 <= int(none[0].max()) < 4 and int(none[1].max()) == 0


class _Mesh:
    """Any object: the rule only asks whether there is one."""


@pytest.mark.parametrize(
    "platform,mesh,rows,k,held,hidden,width,want,tiles", [
        # SDAR's block pass, LFM2's step and its 512 bucket: two whole
        # experts in VMEM, the kernel of PR 31 to the letter
        ("tpu", None, 512, 8, 128, 2048, 768, True, (128, 768)),
        ("tpu", None, 128, 4, 64, 2048, 1536, False, (128, 1536)),
        ("tpu", None, 512, 4, 64, 2048, 1536, True, (128, 1536)),
        ("tpu", None, 384, 8, 128, 2048, 768, True, (128, 768)),
        ("tpu", None, 256, 8, 128, 2048, 768, False, (128, 768)),  # behind
        ("cpu", None, 512, 8, 128, 2048, 768, False, (128, 768)),
        ("tpu", _Mesh(), 512, 8, 128, 2048, 768, False, (128, 768)),
        # GLM-5's 16 of 256 experts of 6,144 x 2,048 at a 16k prefill:
        # two are 151 MB, walked in 4 width tiles of 512 (37.7 MB for
        # two), 256 rows a visit; its step of 16 rows keeps the one form
        ("tpu", None, 16384, 8, 16, 6144, 2048, True, (256, 512)),
        ("tpu", None, 8192, 8, 16, 6144, 2048, True, (256, 512)),
        ("tpu", None, 32768, 8, 16, 6144, 2048, True, (256, 512)),
        ("tpu", None, 16, 8, 16, 6144, 2048, False, (256, 512)),
        # Mixtral's 4,096 x 14,336 (two are 700 MB): 14 tiles of 1,024
        ("tpu", None, 512, 2, 8, 4096, 14336, True, (256, 1024)),
        ("tpu", None, 512, 8, 128, 2048, 800, False, None),  # no whole lanes
        ("tpu", None, 512, 2, 8, 65536, 1024, False, None),  # no tile fits
    ], ids=lambda v: str(v) if not isinstance(v, _Mesh) else "mesh")
def test_the_rule_on_shapes_alone(platform, mesh, rows, k, held, hidden,
                                  width, want, tiles):
    assert grouped_ffn.applicable(platform, mesh, rows, k, held, hidden,
                                  width) is want
    assert grouped_ffn.tiles(hidden, width) == tiles
    assert grouped_ffn.GROUPED_MIN_ROWS == 384
    if tiles:
        tm, wt = tiles
        assert width % wt == 0 and wt % 128 == 0
        assert grouped_ffn._vmem_bytes(hidden, wt, 2, tm, wt < width) \
            <= grouped_ffn._VMEM_CAP
        # a window is whole row tiles; the two sparse chat cells' calls
        # of 512 rows are one window, a long prefill's pairs are many
        win = grouped_ffn.window_pairs(rows, k, hidden, tm)
        assert win % tm == 0
        # (as many as 128 MiB of float32 rows hold)
        assert win == (-(-rows * k // tm) * tm if rows < 8192
                       else 2 ** 27 // (4 * hidden) // tm * tm)
        assert (win < rows * k) is (rows >= 8192)


def test_here_the_rule_keeps_every_expert_on_every_row():
    assert moe.expert_product(512, 8, 128, 2048, 768, jnp.bfloat16) \
        == "every_expert"


# --- the counter that says which form a served program runs --------------------

def _served(net, prompts):
    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerConfig
    from mxnet_tpu.telemetry import tracing

    since = time.perf_counter()
    with serving.GenerativeServer(net, ServerConfig(
            max_batch=1, max_length=64, min_length=8, num_slots=2,
            block_size=4)) as srv:
        toks = [srv.generate(np.arange(1, 1 + n), max_new_tokens=3)
                for n in prompts]
        stats = srv.stats()
    return (toks, stats, tracing.lane_log("decode.tick", since=since),
            tracing.lane_log("prefill.batch", since=since))


@pytest.mark.parametrize("case", ["lfm2_here", "lfm2_as_on_a_chip", "llama"])
def test_expert_product_says_what_ran(case, monkeypatch):
    """``stats()``, the lane's first ``decode.tick`` record and every
    ``prefill.batch`` record: ``every_expert`` here; where the rule admits
    calls of 16 rows and more (a chip's admits 384), the step of 2 slots
    keeps the one form and the prefill buckets of 16 run the kernel, each
    bucket deciding for itself; null for a model without experts."""
    if case == "llama":
        from mxnet_tpu.models.llama import llama_tiny as make
    else:
        from mxnet_tpu.models.lfm2 import lfm2_moe_tiny as make
    net = make()
    net.initialize()
    prompts = (3, 9, 12)                    # buckets of 8, 16 and 16 rows
    want = {"lfm2_here": ["every_expert"] * 3, "llama": [None] * 3,
            "lfm2_as_on_a_chip": ["every_expert", "grouped_kernel",
                                  "grouped_kernel"]}[case]
    step = None if case == "llama" else "every_expert"
    if case == "lfm2_as_on_a_chip":
        plain = _served(net, prompts)[0]
        monkeypatch.setattr(
            grouped_ffn, "applicable",
            lambda platform, mesh, rows, *shapes: rows >= 16)
        _interpreted(monkeypatch)
    toks, stats, ticks, batches = _served(net, prompts)
    assert stats["expert_product"] == step
    assert ticks[0]["expert_product"] == step
    assert "expert_product" not in ticks[-1]
    assert [b["expert_product"] for b in batches] == want
    assert [b["bucket"] for b in batches] == [(1, 8), (1, 16), (1, 16)]
    if case == "lfm2_as_on_a_chip":
        assert [np.asarray(a).tolist() for a in toks] \
            == [np.asarray(a).tolist() for a in plain]


def test_the_experts_compute_other_rows_than_the_router_reads(form):
    """A latent expert layer: the router reads the model's width, the experts
    a projection of it (``rows``), and the result has the projection's
    width."""
    t = _bank(9)
    rs = np.random.RandomState(9)
    wide = (rs.randn(N, 3 * H) * 0.3).astype(np.float32)   # what is routed
    rw = (rs.randn(E, 3 * H) * 0.3).astype(np.float32)
    got, counts = moe.routed_ffn(
        jnp.asarray(wide), jnp.asarray(rw), None, jnp.asarray(t["wu"]),
        jnp.asarray(t["wd"]), 3, score="sigmoid",
        choice_bias=jnp.asarray(t["b"]), scale=5.0, kind="relu2",
        rows=jnp.asarray(t["x"]))
    idx, w = moe.route(jnp.asarray(wide), jnp.asarray(rw), 3, "sigmoid",
                       jnp.asarray(t["b"]), True, 5.0)
    want = np.zeros((N, H), np.float32)
    for n in range(N):
        for e, we in zip(np.asarray(idx)[n], np.asarray(w)[n]):
            u = np.maximum(t["x"][n] @ t["wu"][e], 0.0)
            want[n] += we * ((u * u) @ t["wd"][e])
    assert got.shape == (N, H) and np.abs(got - want).max() < 1e-5
    assert int(counts.sum()) == 3 * N


#: sha256 (16 hex digits) of the jaxpr (source locations taken out) of
#: ``routed_ffn`` in its ``every_expert`` form on the commit before the
#: expert's kind (PR 46, dbf5398), at the shapes above: the default kind's
#: program is the parent's to the letter.  (The kernel's: ``tests/
#: test_train_kernels.py::test_the_windowed_calls_are_the_parents_programs``.)
PARENT_EVERY_EXPERT = "db18483d4ba7150a"


def test_the_swiglu_programs_text_is_the_parents():
    import hashlib
    import re

    import jax

    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32

    def call(x, rw, wg, wu, wd, b, live):
        return moe.routed_ffn(x, rw, wg, wu, wd, 3, score="sigmoid",
                              choice_bias=b, scale=2.5, experts_held=(2, 4),
                              live=live)

    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(call)(
            sds((N, H), f32), sds((E, H), f32), sds((4, H, I), f32),
            sds((4, H, I), f32), sds((4, I, H), f32), sds((E,), f32),
            sds((N,), jnp.bool_)))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_EVERY_EXPERT


def test_a_relu2_bank_has_no_backward_and_says_so(form):
    """Differentiating ``"relu2"`` experts raises an ``MXNetError`` that names
    them, in either form and on every platform alike; ``"swiglu"`` experts
    differentiate as before."""
    import jax

    t = _bank(10)
    args = [jnp.asarray(t[n]) for n in ("x", "rw", "wu", "wd")]

    def loss(x, rw, wu, wd, kind):
        wg = None if kind == "relu2" else wu
        return moe.routed_ffn(x, rw, wg, wu, wd, 2, kind=kind)[0].sum()

    with pytest.raises(mx.MXNetError, match="relu2"):
        jax.grad(loss)(*args, "relu2")
    assert np.isfinite(np.asarray(jax.grad(loss)(*args, "swiglu"))).all()
    # and the kernel called alone refuses for itself
    with pytest.raises(mx.MXNetError, match="relu2"):
        jax.grad(lambda x: grouped_ffn.grouped_expert_ffn(
            x, jnp.zeros((N, 2), jnp.int32), jnp.ones((N, 2), jnp.float32),
            None, args[2], args[3], row_tile=TILE, interpret=True,
            kind="relu2").sum())(args[0])


def test_bad_arguments_are_refused():
    t = _bank(5)
    with pytest.raises(mx.MXNetError, match="takes three banks"):
        moe.routed_ffn(jnp.asarray(t["x"]), jnp.asarray(t["rw"]),
                       jnp.asarray(t["wg"]), jnp.asarray(t["wu"]),
                       jnp.asarray(t["wd"]), 2, kind="relu2")
    with pytest.raises(mx.MXNetError, match="takes three banks"):
        grouped_ffn.grouped_expert_ffn(
            jnp.asarray(t["x"]), jnp.zeros((N, 2), jnp.int32),
            jnp.ones((N, 2), jnp.float32), None, jnp.asarray(t["wu"]),
            jnp.asarray(t["wd"]))
    with pytest.raises(mx.MXNetError, match="unknown router score"):
        _run(t, 2, score="tanh")
    with pytest.raises(mx.MXNetError, match="experts_held says"):
        moe.routed_ffn(jnp.asarray(t["x"]), jnp.asarray(t["rw"]),
                       jnp.asarray(t["wg"]), jnp.asarray(t["wu"]),
                       jnp.asarray(t["wd"]), 2, experts_held=(0, 4))

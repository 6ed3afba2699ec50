"""The dropless routed feed-forward (``models.moe.route`` / ``routed_ffn``)
against a row-by-row numpy oracle: nothing dropped, the choice by score plus
bias and the weights by score alone, the shares of ``experts_held`` adding up
to the uncut layer, and the row counts leaving out rows no request owns.
Every case runs in both of ``routed_ffn``'s forms: every held expert on every
row, and ``ops.grouped_ffn`` (the kernel under the Pallas interpreter, steered
to it as a chip's rule would at hundreds of rows); then the rule on shapes
alone, and the ``expert_product`` counter of a served model.

(Beside ``tests/test_moe.py``, whose module is in the slow lane: these run in
tier 1.)
"""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import moe
from mxnet_tpu.ops import grouped_ffn

N, H, I, E = 24, 16, 12, 8
#: the kernel's row tile here: 24 rows x 1, 3 or 4 experts are 24, 72 or
#: 96 pairs, 16 of them a tile, so the last tile is part padding, most
#: tiles straddle experts and a large group spans several
TILE = 16


#: the kernel under the interpreter, traced once a shape
_INTERPRETED = jax.jit(functools.partial(grouped_ffn._grouped_expert_ffn,
                                         interpret=True),
                       static_argnames=("row_tile",))


def _interpreted(patch, row_tile=TILE):
    patch.setattr(grouped_ffn, "grouped_expert_ffn", functools.partial(
        _INTERPRETED, row_tile=row_tile))


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


def _oracle(t, k, score, bias, renormalize, scale, held=None):
    """Row by row: scores over all experts, the top k of score + bias, the
    weights from the scores alone, the chosen experts' SwiGLUs summed."""
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
            g = t["x"][n] @ t["wg"][e]
            u = t["x"][n] @ t["wu"][e]
            y[n] += we * ((g / (1.0 + np.exp(-g)) * u) @ t["wd"][e])
    return y, counts


def _run(t, k, score="sigmoid", bias=True, renormalize=True, scale=1.0,
         held=None, live=None):
    sl = slice(None) if held is None else slice(held[0], held[0] + held[1])
    y, c = moe.routed_ffn(
        jnp.asarray(t["x"]), jnp.asarray(t["rw"]), jnp.asarray(t["wg"][sl]),
        jnp.asarray(t["wu"][sl]), jnp.asarray(t["wd"][sl]), k, score=score,
        choice_bias=jnp.asarray(t["b"]) if bias else None,
        renormalize=renormalize, scale=scale, experts_held=held,
        live=None if live is None else jnp.asarray(live))
    return np.asarray(y), np.asarray(c)


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("score,bias,renormalize,scale", [
    ("sigmoid", True, True, 1.0),      # the LFM2 router
    ("sigmoid", False, False, 2.5),    # a scale on raw scores
    ("softmax", False, True, 1.0),     # the Mixtral router
    ("softmax", True, False, 1.0),
])
def test_routed_ffn_equals_the_row_by_row_oracle(form, k, score, bias,
                                                 renormalize, scale):
    t = _bank(1)
    got, counts = _run(t, k, score, bias, renormalize, scale)
    want, wc = _oracle(t, k, score, bias, renormalize, scale)
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


@pytest.mark.parametrize("k,score,bias,experts", [
    (2, "sigmoid", True, 8), (3, "sigmoid", True, 8),
    (8, "softmax", False, 16),          # the Qwen3-MoE router's shape
], ids=["2", "3", "8_of_16_softmax"])
@pytest.mark.parametrize("share", [1, 2, 4])
def test_the_shares_of_experts_held_add_up_to_the_uncut_layer(
        form, k, score, bias, experts, share):
    """Eight (or sixteen) experts over chips that hold 1, 2 or 4 each:
    every share routes over all experts and computes its own experts'
    part; the parts add up to the whole layer, and each agrees with the
    oracle restricted to its range."""
    t = _bank(3, experts)
    whole, counts = _run(t, k, score, bias)
    total = np.zeros_like(whole)
    for first in range(0, experts, share):
        part, c = _run(t, k, score, bias, held=(first, share))
        want, _ = _oracle(t, k, score, bias, True, 1.0,
                          held=(first, share))
        assert np.abs(part - want).max() < 1e-5
        assert (c == counts).all()              # routing is over all experts
        total += part
    assert np.abs(total - whole).max() < 1e-5


@pytest.mark.parametrize("owned", ["none", "every_other", "first_five",
                                   "all"])
def test_rows_no_request_owns_are_computed_and_not_counted(form, owned):
    """A served program also routes vacant slots and the padded end of a
    prompt: ``live`` keeps them out of the counts and changes no output."""
    t = _bank(4)
    live = {"none": np.zeros(N, bool), "every_other": np.arange(N) % 2 == 0,
            "first_five": np.arange(N) < 5, "all": np.ones(N, bool)}[owned]
    got, counts = _run(t, 3, live=live)
    every, _ = _run(t, 3)
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


@pytest.mark.parametrize("platform,mesh,rows,k,held,hidden,width,want", [
    ("tpu", None, 512, 8, 128, 2048, 768, True),     # SDAR's block pass
    ("tpu", None, 128, 4, 64, 2048, 1536, False),    # LFM2's step
    ("tpu", None, 512, 4, 64, 2048, 1536, True),     # LFM2's 512 bucket
    ("tpu", None, 384, 8, 128, 2048, 768, True),     # the threshold itself
    ("tpu", None, 256, 8, 128, 2048, 768, False),    # measured: behind
    ("cpu", None, 512, 8, 128, 2048, 768, False),
    ("tpu", _Mesh(), 512, 8, 128, 2048, 768, False),
    ("tpu", None, 512, 2, 8, 4096, 14336, False),    # two experts: 700 MB
    ("tpu", None, 512, 8, 128, 2048, 800, False),    # no whole lanes
], ids=lambda v: str(v) if not isinstance(v, _Mesh) else "mesh")
def test_the_rule_on_shapes_alone(platform, mesh, rows, k, held, hidden,
                                  width, want):
    assert grouped_ffn.applicable(platform, mesh, rows, k, held, hidden,
                                  width) is want
    assert grouped_ffn.GROUPED_MIN_ROWS == 384


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


def test_bad_arguments_are_refused():
    t = _bank(5)
    with pytest.raises(mx.MXNetError, match="unknown router score"):
        _run(t, 2, score="tanh")
    with pytest.raises(mx.MXNetError, match="experts_held says"):
        moe.routed_ffn(jnp.asarray(t["x"]), jnp.asarray(t["rw"]),
                       jnp.asarray(t["wg"]), jnp.asarray(t["wu"]),
                       jnp.asarray(t["wd"]), 2, experts_held=(0, 4))

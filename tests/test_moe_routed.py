"""The dropless routed feed-forward (``models.moe.route`` / ``routed_ffn``)
against a row-by-row numpy oracle: nothing dropped, the choice by score plus
bias and the weights by score alone, the shares of ``experts_held`` adding up
to the uncut layer, and the row counts leaving out rows no request owns.

(Beside ``tests/test_moe.py``, whose module is in the slow lane: these run in
tier 1.)
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import moe

N, H, I, E = 24, 16, 12, 8


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
def test_routed_ffn_equals_the_row_by_row_oracle(k, score, bias,
                                                 renormalize, scale):
    t = _bank(1)
    got, counts = _run(t, k, score, bias, renormalize, scale)
    want, wc = _oracle(t, k, score, bias, renormalize, scale)
    assert np.abs(got - want).max() < 1e-5
    assert (counts == wc).all() and counts.sum() == N * k


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_nothing_is_dropped_when_the_bias_sends_every_token_to_four_experts(
        score):
    """A fixed-capacity layer would drop most of these rows: 24 tokens x 4
    all on the same four of eight experts."""
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
        k, score, bias, experts, share):
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
def test_rows_no_request_owns_are_computed_and_not_counted(owned):
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


def test_bad_arguments_are_refused():
    t = _bank(5)
    with pytest.raises(mx.MXNetError, match="unknown router score"):
        _run(t, 2, score="tanh")
    with pytest.raises(mx.MXNetError, match="experts_held says"):
        moe.routed_ffn(jnp.asarray(t["x"]), jnp.asarray(t["rw"]),
                       jnp.asarray(t["wg"]), jnp.asarray(t["wu"]),
                       jnp.asarray(t["wd"]), 2, experts_held=(0, 4))

"""Plain reference of the LFM2-MoE decoder (``model_type`` ``lfm2_moe``):
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching, and nothing of the program is imported.

The equations, with ``h`` the residual stream and ``norm`` an RMSNorm with a
learned weight (``norm_eps``), no biases anywhere:

* layer ``l``: ``h = h + operator(norm_op(h))``, then ``h = h + ffn(norm_ffn(h))``;
  the operator is ``conv`` or ``full_attention`` by ``layer_types[l]``, the
  feed-forward the dense SwiGLU for ``l < num_dense_layers``, else the experts;
* short convolution: ``[B, C, x] = split3(W_in u)``, ``z = B * x``,
  ``c_t = sum_j w[j] * z_{t-2+j}`` a channel (``conv_L_cache`` 3 taps, causal,
  zeros before the start), ``y = W_out (C * c)``;
* attention: grouped-query; ``q`` and ``k`` pass an RMSNorm over a head's
  channels (one weight of ``head_dim``, shared by the heads) before the rotary
  embedding; causal softmax; output projection;
* dense feed-forward: ``W2 (silu(W1 u) * W3 u)``;
* experts: ``s = sigmoid(W_g u)`` over all experts; the chosen are the top
  ``num_experts_per_tok`` of ``s + b``; their weights are ``s`` (without ``b``)
  there, over their sum plus 1e-6, times ``routed_scaling_factor``; the output
  is the weighted sum of the chosen experts' SwiGLUs.  No capacity: the
  reference computes every expert on every row and weights by a matrix that
  is zero where an expert was not chosen, so nothing can be dropped;
* model: embedding, the layers, a final RMSNorm, the embedding again as head.

Departures (the configuration's ``assumed``): the rotation pairs (2i, 2i+1)
as ``references/llama.py`` does; ``head_dim`` = hidden / heads; the head is
tied; the expert bias is a seeded Normal(0, 0.1), since the released one is
learned and at zero a program that ignored it would pass.

The weights are made here from the seed, a layer at a time and the experts a
group at a time (one float32 expert layer is 2.4 GB; the check runs beside the
served weights); the benchmark hands the same values to the program, never the
other way round.  An expert's matrices come from a key of its own, so any
grouping makes the same values.  ``lowp`` rounds every matrix product's
operands (the router's too) to float8: the control, the step below the
bfloat16 the configuration states.

The shared arithmetic (float8 rounding, RMSNorm, rotation, blocked causal
attention) and ``served_gaps`` are ``references/llama.py``'s own code: that
file is loaded here under a name of its own.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_reference_lfm2_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

_fp8, _mm, _rms, _rope, _attention = (_base._fp8, _base._mm, _base._rms,
                                      _base._rope, _base._attention)
BIAS_STD = 0.1
EXPERT_GROUP = 8      # experts made and computed at a time


def _normal(key, shape, dtype, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dtype)


def layer_kind(cfg, l):
    """(operator, feed-forward) of layer ``l``."""
    return (cfg["layer_types"][l],
            "dense" if l < cfg["num_dense_layers"] else "experts")


def layer_shapes(cfg, kind):
    """Leaf name -> shape, without the expert bank; matrices are (out, in),
    the convolution's taps (tap, channel)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {}
    if kind[0] == "conv":
        out.update(in_proj=(3 * h, h), conv=(cfg["conv_L_cache"], h),
                   out_proj=(h, h))
    else:
        out.update(q=(nq * hd, h), k=(nkv * hd, h), v=(nkv * hd, h),
                   o=(h, nq * hd))
    if kind[1] == "dense":
        f = cfg["intermediate_size"]
        out.update(gate=(f, h), up=(f, h), down=(h, f))
    else:
        out.update(router=(cfg["num_experts"], h))
    return out


def init_experts(key, cfg, dtype, first, count):
    """Experts ``first .. first + count`` of a layer, stacked: ``w_gate`` and
    ``w_up`` (count, hidden, width), ``w_down`` (count, width, hidden), each
    (in, out); every expert's values come from its own key."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]

    def one(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1000 + e), 3)
        return {"w_gate": _normal(kg, (h, i), dtype, cfg),
                "w_up": _normal(ku, (h, i), dtype, cfg),
                "w_down": _normal(kd, (i, h), dtype, cfg)}

    return jax.vmap(one)(first + jnp.arange(count))


def init_layer(key, cfg, dtype, kind, experts=True):
    """One layer's weights from its key; ``experts=False`` leaves the expert
    bank out (the forward pass makes it a group at a time)."""
    shapes = layer_shapes(cfg, kind)
    keys = jax.random.split(key, len(shapes) + 1)
    w = {n: _normal(k, shapes[n], dtype, cfg)
         for k, n in zip(keys, sorted(shapes))}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    w.update(op_norm=ones, ffn_norm=ones)
    if kind[0] == "full_attention":
        w.update(q_norm=jnp.ones((cfg["head_dim"],), dtype),
                 k_norm=jnp.ones((cfg["head_dim"],), dtype))
    if kind[1] == "experts":
        w["expert_bias"] = (jax.random.normal(
            keys[-1], (cfg["num_experts"],), jnp.float32)
            * BIAS_STD).astype(dtype)
        if experts:
            w.update(init_experts(key, cfg, dtype, 0, cfg["num_experts"]))
    return w


def init_top(key, cfg, dtype):
    return {"emb": _normal(key, (cfg["vocab_size"], cfg["hidden_size"]), dtype,
                           cfg),
            "norm": jnp.ones((cfg["hidden_size"],), dtype)}


layer_key, top_key = _base.layer_key, _base.top_key


def short_conv(u, w, cfg, lowp):
    """u (T, hidden) -> (T, hidden)."""
    h = cfg["hidden_size"]
    taps = cfg["conv_L_cache"]
    bcx = _mm(u, w["in_proj"], lowp)
    b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    z = b * x
    t = z.shape[0]
    zp = jnp.concatenate([jnp.zeros((taps - 1, h), z.dtype), z])
    conv = sum(w["conv"][j] * zp[j:j + t] for j in range(taps))
    return _mm(c * conv, w["out_proj"], lowp)


def attention(u, w, cfg, lowp):
    hd, theta = cfg["head_dim"], cfg["rope_parameters"]["rope_theta"]
    t = u.shape[0]
    q = _rms(_mm(u, w["q"], lowp).reshape(t, -1, hd), w["q_norm"],
             cfg["norm_eps"])
    k = _rms(_mm(u, w["k"], lowp).reshape(t, -1, hd), w["k_norm"],
             cfg["norm_eps"])
    v = _mm(u, w["v"], lowp).reshape(t, -1, hd)
    ctx = _attention(_rope(q, theta), _rope(k, theta), v, lowp).reshape(t, -1)
    return _mm(ctx, w["o"], lowp)


def combine_weights(u, w, cfg, lowp):
    """-> ((T, experts) float32: an expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in ``s + b``)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(u, w["router"], lowp))
    pick = s + w["expert_bias"] if cfg["use_expert_bias"] else s
    lead, idx = jax.lax.top_k(pick, k + 1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].add(chosen), margin


def experts_part(u, comb, bank, lowp):
    """What the experts of ``bank`` add for rows u (N, hidden): every one of
    them on every row, weighted by its columns of ``comb`` (N, count)."""
    def one(acc, e):
        wg, wu, wd, c = e
        g = _mm(u, wg.T, lowp)
        y = _mm(jax.nn.silu(g) * _mm(u, wu.T, lowp), wd.T, lowp)
        return acc + c[:, None] * y, None

    bank = {n: a.astype(jnp.float32) for n, a in bank.items()}
    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (bank["w_gate"], bank["w_up"], bank["w_down"],
                           comb.T))
    return acc


def layer_front(x, w, cfg, kind, lowp=False):
    """The operator with its residual, then either the whole dense
    feed-forward (-> (x, None, None, None)) or what the experts need: (x, the
    normed rows, the combine weights, the choice margins).  x (T, hidden)
    float32."""
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    h = _rms(x, w["op_norm"], cfg["norm_eps"])
    op = short_conv if kind[0] == "conv" else attention
    x = x + op(h, w, cfg, lowp)
    h = _rms(x, w["ffn_norm"], cfg["norm_eps"])
    if kind[1] == "dense":
        g = _mm(h, w["gate"], lowp)
        return x + _mm(jax.nn.silu(g) * _mm(h, w["up"], lowp), w["down"],
                       lowp), None, None, None
    return (x, h) + combine_weights(h, w, cfg, lowp)


def layer_forward(x, w, cfg, kind, lowp=False):
    """One whole layer over one sequence from a full set of weights (the
    tests' sizes; ``forward_rows`` makes the experts in groups instead)."""
    x, h, comb, _margin = layer_front(x, w, cfg, kind, lowp)
    if h is None:
        return x
    return x + experts_part(h, comb, {n: w[n] for n in
                                      ("w_gate", "w_up", "w_down")}, lowp)


def forward(cfg, weights, ids, lowp=False):
    """Logits (T, vocab) of one sequence from given weights
    ``{"top": ..., "layers": [...]}``."""
    with jax.default_matmul_precision("highest"):
        x = weights["top"]["emb"].astype(jnp.float32)[jnp.asarray(ids)]
        for l, w in enumerate(weights["layers"]):
            x = layer_forward(x, w, cfg, layer_kind(cfg, l), lowp)
        h = _rms(x, weights["top"]["norm"].astype(jnp.float32),
                 cfg["norm_eps"])
        return _mm(h, weights["top"]["emb"].astype(jnp.float32), lowp)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, dtype_name, lowp):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return top["emb"].astype(jnp.float32)[ids]

    @functools.partial(jax.jit, static_argnums=3)
    def front(seed_key, l, xs, kind):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, kind, experts=False)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda x: layer_front(x, w, cfg, kind, lowp), xs)

    @functools.partial(jax.jit, donate_argnums=5)
    def group(seed_key, l, first, hs, combs, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype, first,
                            EXPERT_GROUP)
        n, t, h = hs.shape
        comb = jax.lax.dynamic_slice_in_dim(combs.reshape(n * t, -1), first,
                                            EXPERT_GROUP, axis=1)
        with jax.default_matmul_precision("highest"):
            return acc + experts_part(hs.reshape(n * t, h), comb, bank,
                                      lowp).reshape(n, t, h)

    @jax.jit
    def logits(seed_key, xs, rows, margins):
        top = init_top(top_key(seed_key), cfg, dtype)
        picked = xs[rows[:, 0], rows[:, 1]]
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, top["norm"].astype(jnp.float32), cfg["norm_eps"])
            return _mm(h, top["emb"].astype(jnp.float32), lowp), \
                margins[rows[:, 0], rows[:, 1]]

    return embed, front, group, logits


def _cfg_json(cfg):
    keep = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "head_dim", "num_attention_heads", "num_key_value_heads",
            "vocab_size", "rope_parameters", "norm_eps", "num_hidden_layers",
            "num_dense_layers", "layer_types", "conv_L_cache", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, lowp=False, with_margin=False):
    """Logits of the full forward pass at chosen positions, weights remade
    from the seed: ``ids`` (N, T) int32, every sequence padded at its end;
    ``rows`` (M, 2) pairs (sequence, position).  A layer's weights, and of
    its experts a group's, on the device at a time.  -> (M, vocab) float32
    on the host; ``with_margin`` adds (M,): the smallest choice margin of the
    row's token over the expert layers (``combine_weights``)."""
    embed, front, group, logits = _programs(_cfg_json(cfg), cfg["torch_dtype"],
                                            bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    xs = embed(key, jnp.asarray(ids, jnp.int32))
    assert cfg["num_experts"] % EXPERT_GROUP == 0
    margins = jnp.full(xs.shape[:2], jnp.inf, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        xs, hs, combs, margin = front(key, jnp.int32(l), xs,
                                      layer_kind(cfg, l))
        if hs is not None:
            margins = jnp.minimum(margins, margin)
            acc = jnp.zeros_like(xs)
            for first in range(0, cfg["num_experts"], EXPERT_GROUP):
                acc = group(key, jnp.int32(l), jnp.int32(first), hs, combs, acc)
            xs = xs + acc
    out, margin = logits(key, xs, jnp.asarray(rows), margins)
    return (np.asarray(out), np.asarray(margin)) if with_margin \
        else np.asarray(out)


def served_gaps(cfg, seed, prompts, served, pad_to, max_rows,
                lowp_control=False, with_margin=False):
    """``served_gaps`` as the Llama reference decides it, over this file's
    forward pass: the private copy of that module calls ``forward_rows`` by
    its global name, which is bound here.  ``with_margin``: -> (gaps, the
    float32 pass's choice margin at each of those tokens).  A token whose
    margin is small may go to another expert under bfloat16 activations, and
    its gap then says nothing of the program's arithmetic."""
    kept = {}

    def rows_fn(cfg, seed, ids, rows, lowp=False):
        out, margin = forward_rows(cfg, seed, ids, rows, lowp,
                                   with_margin=True)
        if not lowp:
            kept["margin"] = margin
        return out

    _base.forward_rows = rows_fn
    gaps = _base.served_gaps(cfg, seed, prompts, served, pad_to, max_rows,
                             lowp_control)
    return (gaps, kept["margin"][:len(gaps)]) if with_margin else gaps

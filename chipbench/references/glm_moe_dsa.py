"""Plain reference of the GLM-MoE-DSA decoder (``model_type`` ``glm_moe_dsa``):
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching, no absorption, and nothing of the program is
imported.

The equations, with ``x`` the residual stream, ``norm`` an RMSNorm with a
learned weight (``rms_norm_eps``), rotary pairs (2i, 2i+1) and ``h =
norm_attn(x)``:

* latent attention, expanded: ``c_q = norm(h W_qa)``; ``q = c_q W_qb``, a head
  ``qk_nope_head_dim + qk_rope_head_dim`` wide, rotated on the rope part;
  ``[c_kv | k_r] = h W_kva``, ``c_kv = norm(c_kv)``, ``k_r`` rotated, one for all
  heads; ``[k_nope_h | v_h] = c_kv W_kvb``; ``score_h[t, s] = (q_nope_h[t] .
  k_nope_h[s] + q_rope_h[t] . k_r[s]) / sqrt(nope + rope)`` over ``s`` in
  ``S_t``; softmax; ``o = concat_h(sum_s p_h v_h) W_o``;
* the indexer: ``q_I = c_q W_Iq`` (``index_n_heads`` x ``index_head_dim``),
  ``k_I = LayerNorm(h W_Ik)`` (eps 1e-6, weight and bias), both rotated on their
  first ``qk_rope_head_dim`` values, ``w = h W_Iw * index_n_heads^-1/2 *
  index_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``;
  ``S_t`` = the ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``
  (``lax.top_k`` over the whole row gives the smallest value taken; equal
  values go to the earlier position), all of them while ``t < index_topk``;
* feed-forward: a dense SwiGLU of ``intermediate_size`` for ``l <
  first_k_dense_replace``; else ``s = sigmoid(W_r u)`` over all
  ``router_experts``, the chosen the top ``num_experts_per_tok`` of ``s + b``,
  their weights ``s`` there over their sum (+1e-6) times
  ``routed_scaling_factor``, the output the weighted sum of the chosen experts'
  SwiGLUs that lie in ``experts_held`` (first, count) (what the other chips'
  experts would add is left out) plus the shared expert's SwiGLU;
* model: embedding, the layers, a final RMSNorm, an untied head over the
  ``vocab_size`` rows held.

Departures (the configuration's ``assumed``): what the published ``config`` does
not settle is taken from the DeepSeek-V3.2 inference reference that the family
follows (the LayerNorm on the index key, the two scales on ``w``, the rotary
part first in an index head); the indexer's FP8 and its rotation of q and k are
left out (the rotation leaves every product unchanged); the choice bias is a
seeded Normal(0, 0.1), since the released one is learned and at zero a program
that ignored it would pass; the multi-token-prediction layer is not part of the
forward.

Sized for a 28k-token request beside the served weights: the selection is
computed in blocks of query rows and kept as packed bits, attention in groups
of heads and blocks of query rows, the dense feed-forward in blocks of rows, the
held experts one at a time, a layer's weights on the device at a time; an
expert's matrices come from a key of its own.  ``lowp`` rounds every matrix
product's operands to float8: the control, the step below the bfloat16 the
configuration states.  ``select="recent"`` is the second control: the
``index_topk`` most recent positions instead of the indexer's.

The shared arithmetic (float8 rounding, RMSNorm) and ``served_gaps`` are
``references/llama.py``'s own code: that file is loaded here under a name of its
own.
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
    "chipbench_reference_glm_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

_fp8, _mm, _rms = _base._fp8, _base._mm, _base._rms
layer_key, top_key = _base.layer_key, _base.top_key

BIAS_STD = 0.1
INDEX_NORM_EPS = 1e-6
Q_BLOCK = 256         # query rows scored, selected and attended at a time
KEY_GROUP = 32        # blocks of query rows that share one extent of keys
HEAD_GROUP = 4        # heads whose keys and values are expanded at a time
ROW_BLOCK = 2048      # rows of a dense feed-forward at a time
EXPERT_GROUP = 8      # experts made at a time


def _normal(key, shape, dtype, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dtype)


def layer_kind(cfg, l):
    return "dense" if l < cfg["first_k_dense_replace"] else "experts"


def layer_shapes(cfg, kind):
    """Leaf name -> shape, without the norms, the bias and the expert bank;
    matrices are (out, in)."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    out = {"q_a": (ql, h), "q_b": (nh * (dn + dr), ql), "kv_a": (kl + dr, h),
           "kv_b": (nh * (dn + dv), kl), "o": (h, nh * dv),
           "idx_q": (ih * idim, ql), "idx_k": (idim, h), "idx_w": (ih, h)}
    if kind == "dense":
        f = cfg["intermediate_size"]
        out.update(gate=(f, h), up=(f, h), down=(h, f))
    else:
        s = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
        out.update(router=(cfg["router_experts"], h), shared_gate=(s, h),
                   shared_up=(s, h), shared_down=(h, s))
    return out


def init_experts(key, cfg, dtype, first, count):
    """Experts ``first .. first + count`` OF THE LAYER (not of the held part),
    stacked: ``w_gate`` and ``w_up`` (count, hidden, width), ``w_down`` (count,
    width, hidden), each (in, out); every expert's values come from its own
    key, so any division of the layer over chips makes the same values."""
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
    w.update(attn_norm=jnp.ones((cfg["hidden_size"],), dtype),
             ffn_norm=jnp.ones((cfg["hidden_size"],), dtype),
             q_a_norm=jnp.ones((cfg["q_lora_rank"],), dtype),
             kv_a_norm=jnp.ones((cfg["kv_lora_rank"],), dtype),
             idx_k_norm=jnp.ones((cfg["index_head_dim"],), dtype),
             idx_k_bias=jnp.zeros((cfg["index_head_dim"],), dtype))
    if kind == "experts":
        w["expert_bias"] = (jax.random.normal(
            keys[-1], (cfg["router_experts"],), jnp.float32)
            * BIAS_STD).astype(dtype)
        if experts:
            w.update(init_experts(key, cfg, dtype, *cfg["experts_held"]))
    return w


def init_top(key, cfg, dtype):
    ke, kh = jax.random.split(key)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"emb": _normal(ke, (v, h), dtype, cfg),
            "head": _normal(kh, (v, h), dtype, cfg),
            "norm": jnp.ones((h,), dtype)}


# -- the layer ----------------------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rope_at(x, pos, theta):
    """x (T, .., D) rotated in pairs (2i, 2i+1) by ``pos`` (T,) * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d)),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _layer_norm(x, w, b):
    x = x - x.mean(axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + INDEX_NORM_EPS) * w + b


def _blocks(t, want):
    """The largest block of at most ``want`` rows that divides ``t``."""
    return max(b for b in range(1, min(want, t) + 1) if t % b == 0)


def _over_query_blocks(fn, t, blk):
    """``fn(i, extent)`` for every block ``i`` of ``blk`` query rows, stacked
    in order.  A block reads no key past its own end, so the blocks go in
    groups of ``KEY_GROUP`` and a group's ``extent`` (static, a multiple of 8)
    is its last row's: the causal half of the work is not done twice."""
    n, out = t // blk, []
    for first in range(0, n, KEY_GROUP):
        last = min(first + KEY_GROUP, n)
        extent = min(-(-last * blk // 8) * 8, t)
        out.append(jax.lax.map(functools.partial(fn, extent=extent),
                               jnp.arange(first, last)))
    return jnp.concatenate(out)


def attention_inputs(x, w, cfg, lowp):
    """What every query block shares: ``c_q`` (T, q_lora), the latent rows
    ``[c_kv | k_r]`` (T, kv_lora + rope), the index keys (T, index_head_dim)
    and the index weights (T, index_n_heads)."""
    kl, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_parameters"]["rope_theta"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(x.shape[0])
    h = _rms(x, _f32(w["attn_norm"]), eps)
    c_q = _rms(_mm(h, _f32(w["q_a"]), lowp), _f32(w["q_a_norm"]), eps)
    kv = _mm(h, _f32(w["kv_a"]), lowp)
    latent = jnp.concatenate(
        [_rms(kv[:, :kl], _f32(w["kv_a_norm"]), eps),
         _rope_at(kv[:, kl:], pos, theta)], axis=-1)
    k_idx = _layer_norm(_mm(h, _f32(w["idx_k"]), lowp), _f32(w["idx_k_norm"]),
                        _f32(w["idx_k_bias"]))
    k_idx = jnp.concatenate([_rope_at(k_idx[:, :dr], pos, theta), k_idx[:, dr:]],
                            axis=-1)
    w_idx = _mm(h, _f32(w["idx_w"]), lowp) \
        * (cfg["index_n_heads"] ** -0.5 * cfg["index_head_dim"] ** -0.5)
    return c_q, latent, k_idx, w_idx


def selection(c_q, k_idx, w_idx, w, cfg, lowp, select="indexer"):
    """``S_t`` of every row as packed bits: (T, ceil(T / 8)) uint8, bit ``s`` of
    row ``t`` (``numpy.packbits`` order) set where ``s`` is selected.  In blocks
    of query rows, the index heads one at a time."""
    t = c_q.shape[0]
    k = min(cfg["index_topk"], t)
    ih, idim, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["qk_rope_head_dim"])
    theta = cfg["rope_parameters"]["rope_theta"]
    blk = _blocks(t, Q_BLOCK)
    cols = jnp.arange(t)
    w_q = _f32(w["idx_q"]).reshape(ih, idim, -1)

    width = -(-t // 8)
    keys = _fp8(k_idx) if lowp else k_idx

    def block(i, extent):
        rows = i * blk + jnp.arange(blk)
        visible = cols[None, :extent] <= rows[:, None]
        if select == "recent":
            chosen = visible & (cols[None, :extent] > rows[:, None] - k)
        else:
            cq = jax.lax.dynamic_slice_in_dim(c_q, i * blk, blk, axis=0)
            wi = jax.lax.dynamic_slice_in_dim(w_idx, i * blk, blk, axis=0)

            def head(acc, j):
                q = _mm(cq, w_q[j], lowp)                           # (blk, idim)
                q = jnp.concatenate([_rope_at(q[:, :dr], rows, theta),
                                     q[:, dr:]], axis=-1)
                if lowp:
                    q = _fp8(q)
                return acc + wi[:, j, None] * jax.nn.relu(q @ keys[:extent].T), \
                    None

            scores, _ = jax.lax.scan(head, jnp.zeros((blk, extent), jnp.float32),
                                     jnp.arange(ih))
            scores = jnp.where(visible, scores, -jnp.inf)
            kk = min(k, extent)
            least = jax.lax.top_k(scores, kk)[0][:, -1:]
            above = scores > least
            tied = (scores == least) & visible
            room = kk - above.sum(axis=-1, keepdims=True)
            # of the positions that tie with the smallest taken, the earliest
            chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
        bits = jnp.packbits(chosen, axis=-1)
        return jnp.pad(bits, ((0, 0), (0, width - bits.shape[-1])))

    return _over_query_blocks(block, t, blk).reshape(t, width)


def attention(x, c_q, latent, chosen_bits, w, cfg, lowp):
    """``x + attention``: expanded keys and values, a group of heads at a time,
    each in blocks of query rows under the selection's mask."""
    t = x.shape[0]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kl = cfg["kv_lora_rank"]
    theta = cfg["rope_parameters"]["rope_theta"]
    hg = _blocks(nh, HEAD_GROUP)
    blk = _blocks(t, Q_BLOCK)
    pos = jnp.arange(t)
    c_kv, k_r = latent[:, :kl], latent[:, kl:]
    w_q = w["q_b"].reshape(nh // hg, hg * (dn + dr), -1)
    w_kv = w["kv_b"].reshape(nh // hg, hg * (dn + dv), kl)
    w_o = w["o"].reshape(-1, nh // hg, hg * dv).transpose(1, 0, 2)
    scale = (dn + dr) ** -0.5

    def group(acc, ws):
        wq, wkv, wo = (_f32(a) for a in ws)
        q = _mm(c_q, wq, lowp).reshape(t, hg, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope_at(q[..., dn:], pos, theta)
        kv = _mm(c_kv, wkv, lowp).reshape(t, hg, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        if lowp:
            q_nope, q_rope, k_nope, v, k_r8 = (_fp8(a) for a in
                                               (q_nope, q_rope, k_nope, v, k_r))
        else:
            k_r8 = k_r

        def block(i, extent):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * blk, blk, axis=0)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, i * blk, blk, axis=0)
            bits = jax.lax.dynamic_slice_in_dim(chosen_bits, i * blk, blk, axis=0)
            chosen = jnp.unpackbits(bits[:, :-(-extent // 8)], axis=-1,
                                    count=extent).astype(bool)
            s = (jnp.einsum("qhd,thd->hqt", qn, k_nope[:extent])
                 + jnp.einsum("qhd,td->hqt", qr, k_r8[:extent])) * scale
            p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
            if lowp:
                p = _fp8(p)
            return jnp.einsum("hqt,thd->qhd", p, v[:extent])

        ctx = _over_query_blocks(block, t, blk).reshape(t, hg * dv)
        return acc + _mm(ctx, wo, lowp), None

    out, _ = jax.lax.scan(group, x, (w_q, w_kv, w_o))
    return out


def _swiglu(u, gate, up, down, lowp):
    g = _mm(u, gate, lowp)
    return _mm(jax.nn.silu(g) * _mm(u, up, lowp), down, lowp)


def _in_row_blocks(fn, u):
    blk = _blocks(u.shape[0], ROW_BLOCK)
    return jax.lax.map(fn, u.reshape(-1, blk, u.shape[-1])).reshape(u.shape)


def combine_weights(u, w, cfg, lowp):
    """-> ((T, count) float32: a HELD expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in ``s + b``)."""
    k = cfg["num_experts_per_tok"]
    first, count = cfg["experts_held"]
    s = jax.nn.sigmoid(_mm(u, _f32(w["router"]), lowp))
    lead, idx = jax.lax.top_k(s + _f32(w["expert_bias"]), k + 1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    comb = jnp.zeros_like(s).at[rows, idx].add(chosen)
    return comb[:, first:first + count], margin


def experts_part(u, comb, bank, lowp):
    """What the experts of ``bank`` add for rows u (N, hidden): every one of
    them on every row, weighted by its columns of ``comb`` (N, count)."""
    def one(acc, e):
        wg, wu, wd, c = e
        y = _swiglu(u, _f32(wg).T, _f32(wu).T, _f32(wd).T, lowp)
        return acc + c[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (bank["w_gate"], bank["w_up"], bank["w_down"], comb.T))
    return acc


def ffn_front(x, w, cfg, kind, lowp):
    """A dense layer: -> (x + feed-forward, None, None, None).  An expert layer:
    -> (x + the shared expert, the normed rows, the held experts' combine
    weights, the choice margins)."""
    u = _rms(x, _f32(w["ffn_norm"]), cfg["rms_norm_eps"])
    if kind == "dense":
        names = ("gate", "up", "down")
    else:
        names = ("shared_gate", "shared_up", "shared_down")
    mats = [_f32(w[n]) for n in names]
    x = x + _in_row_blocks(lambda r: _swiglu(r, *mats, lowp), u)
    if kind == "dense":
        return x, None, None, None
    return (x, u) + combine_weights(u, w, cfg, lowp)


def layer_forward(x, w, cfg, kind, lowp=False, select="indexer"):
    """One whole layer over one sequence from a full set of weights ->
    (x, the selection's packed bits, the choice margins or None)."""
    c_q, latent, k_idx, w_idx = attention_inputs(x, w, cfg, lowp)
    bits = selection(c_q, k_idx, w_idx, w, cfg, lowp, select)
    x = attention(x, c_q, latent, bits, w, cfg, lowp)
    x, u, comb, margin = ffn_front(x, w, cfg, kind, lowp)
    if u is not None:
        x = x + experts_part(u, comb, {n: w[n] for n in
                                       ("w_gate", "w_up", "w_down")}, lowp)
    return x, bits, margin


def forward(cfg, weights, ids, lowp=False, select="indexer", with_selection=False):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}``; ``with_selection`` adds (layers, T, T) bool.  One
    compiled program a configuration and length (the tests' sizes)."""
    return _forward_program(json.dumps(cfg, sort_keys=True), bool(lowp), select,
                            bool(with_selection))(weights, jnp.asarray(ids))


@functools.lru_cache(maxsize=None)
def _forward_program(cfg_json, lowp, select, with_selection):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda weights, ids: _forward(cfg, weights, ids, lowp, select,
                                                 with_selection))


def _forward(cfg, weights, ids, lowp, select, with_selection):
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["top"]["emb"])[jnp.asarray(ids)]
        chosen = []
        for l, w in enumerate(weights["layers"]):
            x, bits, _m = layer_forward(x, w, cfg, layer_kind(cfg, l), lowp,
                                        select)
            chosen.append(jnp.unpackbits(bits, axis=-1,
                                         count=x.shape[0]).astype(bool))
        h = _rms(x, _f32(weights["top"]["norm"]), cfg["rms_norm_eps"])
        out = _mm(h, _f32(weights["top"]["head"]), lowp)
    return (out, jnp.stack(chosen)) if with_selection else out


# -- the forward pass of the check, a layer's weights at a time ------------------

@functools.lru_cache(maxsize=None)
def _programs(cfg_json, dtype_name, lowp):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return _f32(top["emb"])[ids]

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def select_fn(seed_key, l, x, kind, select):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, kind, experts=False)
        with jax.default_matmul_precision("highest"):
            c_q, latent, k_idx, w_idx = attention_inputs(x, w, cfg, lowp)
            return c_q, latent, selection(c_q, k_idx, w_idx, w, cfg, lowp, select)

    @functools.partial(jax.jit, static_argnums=6, donate_argnums=2)
    def attend_fn(seed_key, l, x, c_q, latent, bits, kind):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, kind, experts=False)
        with jax.default_matmul_precision("highest"):
            x = attention(x, c_q, latent, bits, w, cfg, lowp)
            return ffn_front(x, w, cfg, kind, lowp)

    @functools.partial(jax.jit, donate_argnums=5)
    def group(seed_key, l, first, u, comb, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype,
                            cfg["experts_held"][0] + first, EXPERT_GROUP)
        part = jax.lax.dynamic_slice_in_dim(comb, first, EXPERT_GROUP, axis=1)
        with jax.default_matmul_precision("highest"):
            return acc + experts_part(u, part, bank, lowp)

    @jax.jit
    def logits(seed_key, picked):
        top = init_top(top_key(seed_key), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, _f32(top["norm"]), cfg["rms_norm_eps"])
            return _mm(h, _f32(top["head"]), lowp)

    return embed, select_fn, attend_fn, group, logits


def _cfg_json(cfg):
    keep = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "router_experts", "experts_held", "num_experts_per_tok",
            "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
            "rms_norm_eps", "rope_parameters", "vocab_size", "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, lowp=False, with_margin=False,
                 select="indexer", selected_at=None):
    """Logits of the full forward pass at chosen positions, weights remade from
    the seed: ``ids`` (N, T) int32, every sequence padded at its end; ``rows``
    (M, 2) pairs (sequence, position).  One sequence at a time, a layer's
    weights, and of its experts a group's, on the device at a time.  -> (M,
    vocab) float32 on the host; ``with_margin`` adds (M,): the smallest choice
    margin of the row's token over the expert layers; ``selected_at`` (N,)
    positions adds (layers, N, T) bool: what each layer selects for that row of
    each sequence."""
    embed, select_fn, attend_fn, group, logits = _programs(
        _cfg_json(cfg), cfg["torch_dtype"], bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    held = cfg["experts_held"][1]
    assert held % EXPERT_GROUP == 0
    ids, rows = np.asarray(ids, np.int32), np.asarray(rows)
    n, t = ids.shape
    picked = jnp.zeros((len(rows), cfg["hidden_size"]), jnp.float32)
    margins = np.full(len(rows), np.inf, np.float32)
    chosen = np.zeros((cfg["num_hidden_layers"], n, t), bool)
    for i in range(n):
        mine = rows[:, 0] == i
        if not mine.any() and selected_at is None:
            continue
        x = embed(key, jnp.asarray(ids[i]))
        margin = jnp.full((t,), jnp.inf, jnp.float32)
        for l in range(cfg["num_hidden_layers"]):
            kind = layer_kind(cfg, l)
            c_q, latent, bits = select_fn(key, jnp.int32(l), x, kind, select)
            if selected_at is not None:
                chosen[l, i] = np.unpackbits(
                    np.asarray(bits[int(selected_at[i])]), count=t).astype(bool)
            x, u, comb, m = attend_fn(key, jnp.int32(l), x, c_q, latent, bits,
                                      kind)
            del c_q, latent, bits
            if u is not None:
                margin = jnp.minimum(margin, m)
                acc = jnp.zeros_like(x)
                for first in range(0, held, EXPERT_GROUP):
                    acc = group(key, jnp.int32(l), jnp.int32(first), u, comb, acc)
                x = x + acc
                del u, comb, acc
        at = jnp.asarray(np.where(mine, rows[:, 1], 0))
        picked = jnp.where(jnp.asarray(mine)[:, None], x[at], picked)
        margins = np.where(mine, np.asarray(margin[at]), margins)
        del x
    out = [np.asarray(logits(key, picked))]
    if with_margin:
        out.append(margins)
    if selected_at is not None:
        out.append(chosen)
    return out[0] if len(out) == 1 else tuple(out)


def served_gaps(cfg, seed, prompts, served, pad_to, max_rows, lowp_control=False,
                select_control=None, selected_at=None):
    """``served_gaps`` as the Llama reference decides it, over this file's
    forward pass: the private copy of that module calls ``forward_rows`` by its
    global name, which is bound here.  -> (gaps, info): ``info["margin"]`` the
    float32 pass's choice margin at each of those tokens (a token whose margin
    is small may go to another expert under bfloat16 activations, and its gap
    then says nothing of the program's arithmetic); with ``selected_at`` (a
    position a request) ``info["chosen"]``, what the float32 pass's layers
    select there, (layers, N, pad_to) bool.  ``lowp_control`` puts a control in
    the program's place: the float8 reference, or with ``select_control``
    (``"recent"``) the float32 reference under that selection;
    ``info["control_chosen"]`` is what the control selects."""
    info = {}

    def rows_fn(cfg, seed, ids, rows, lowp=False):
        kw = {} if not lowp else {"select": select_control} if select_control \
            else {"lowp": True}
        out = forward_rows(cfg, seed, ids, rows, with_margin=True,
                           selected_at=selected_at, **kw)
        if not lowp:
            info["margin"] = out[1]
        if selected_at is not None:
            info["control_chosen" if lowp else "chosen"] = out[2]
        return out[0]

    _base.forward_rows = rows_fn
    gaps = _base.served_gaps(cfg, seed, prompts, served, pad_to, max_rows,
                             lowp_control)
    info["margin"] = info["margin"][:len(gaps)]
    return gaps, info

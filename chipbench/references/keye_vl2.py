"""Plain reference of Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``):
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching, no tiling of the function, and nothing of the
program is imported.

The equations, with ``x`` the residual stream, ``N(x) = w x / rms(x)``
(``rms_norm_eps``), no bias but the index key norm's; every layer alike:

* ``u = N_a(x)``; ``q = u W_q`` (``num_attention_heads`` heads of ``head_dim``),
  ``k = u W_k``, ``v = u W_v`` (``num_key_value_heads`` heads); ``q_h <-
  RoPE(N_q(q_h))``, ``k_g <- RoPE(N_k(k_g))``: an RMSNorm over a head's values,
  one weight for all heads, before the rotation;
* RoPE with ``rope_theta`` over the whole head, pairs ``(i, i + head_dim / 2)``.
  A token has a position triple ``(p_t, p_h, p_w)``; frequency ``i`` turns by
  ``p_t`` for ``i < s_0``, by ``p_h`` for ``s_0 <= i < s_0 + s_1``, by ``p_w``
  for the rest (``rope_scaling.mrope_section`` ``[s_0, s_1, s_2]``, chunked).
  Without triples the three are the token's index: the ordinary rotation;
* the indexer (``sa_config``): ``q_I = u W_Iq`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``k_I = LayerNorm(u W_Ik)`` (one for all heads, weight
  and bias, eps 1e-6), both rotated over all their values (pairs ``(i, i + D /
  2)``, by ``p_t``), ``w = u W_Iw * indexer_num_heads^-1/2 *
  indexer_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``;
  ``S_t`` = the ``topk`` positions ``s <= t`` of largest ``I[t, s]``
  (``lax.top_k`` over the whole row gives the smallest value taken; equal values
  go to the earlier position), all of them while ``t + 1 <= topk``;
* attention: ``score_h[t, s] = q_h[t] . k_{h // g}[s] / sqrt(head_dim)`` over
  ``s`` in ``S_t`` (``g`` query heads to a key-value head), softmax, ``o =
  concat_h(sum_s p_h[t, s] v_{h // g}[s]) W_o``; ``x <- x + o``;
* experts, ``u = N_f(x)``: ``s = softmax(W_r u)`` over all ``num_experts``, the
  chosen the ``num_experts_per_tok`` largest (a tie to the lower expert), their
  weights ``s`` there over their sum (``norm_topk_prob``); ``x <- x + sum_e w_e
  (silu(u G_e) * (u U_e)) D_e``.  No shared expert, no dense layer;
* model: ``h_0 = Emb[ids]``, the layers, ``logits = N_o(h_L) W_head`` (untied).

Departures (the configuration's ``assumed``): the head norms (the Qwen3-MoE
family has them always), the index key's LayerNorm and the two scales on ``w``
(the DeepSeek-V3.2 inference reference), the indexer's queries from ``u`` (there
is no query latent), the rotation of all the index values, the chunked order of
``mrope_section``, the published FP8 of the index keys left out; the vision
tower is not here (token ids in).

Sized for a 29k-token request beside the served weights and pool: the
selection is computed in blocks of query rows and kept as packed bits,
attention a key-value head's query heads at a time in blocks of query rows, the
experts a group at a time (every one on every row), the logits in chunks of
rows and of the vocabulary, a layer's weights on the device at a time; an
expert's matrices come from a key of its own.  ``lowp`` rounds every matrix
product's operands to float8: the control, the step below the bfloat16 the
configuration states.  ``select="recent"`` is the second control: the ``topk``
most recent positions instead of the indexer's.

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
    "chipbench_reference_keye_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

_fp8, _mm, _rms = _base._fp8, _base._mm, _base._rms
layer_key, top_key = _base.layer_key, _base.top_key

INDEX_NORM_EPS = 1e-6
Q_BLOCK = 256         # query rows scored, selected and attended at a time
KEY_GROUP = 29        # blocks of query rows that share one extent of keys
EXPERT_GROUP = 8      # experts made and computed at a time
ROW_CHUNK = 512       # rows whose logits are on the device at a time
VOCAB_CHUNKS = 8      # parts of the head cast to float32 at a time


def _normal(key, shape, dtype, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dtype)


def layer_kind(cfg, l):
    return "experts"


def _index(cfg):
    sa = cfg["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def layer_shapes(cfg):
    """Leaf name -> shape, without the norms, the bias and the expert bank;
    matrices are (out, in)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ih, idim, _ = _index(cfg)
    return {"q": (nq * hd, h), "k": (nkv * hd, h), "v": (nkv * hd, h),
            "o": (h, nq * hd), "idx_q": (ih * idim, h), "idx_k": (idim, h),
            "idx_w": (ih, h), "router": (cfg["num_experts"], h)}


def init_experts(key, cfg, dtype, first, count):
    """Experts ``first .. first + count`` of the layer, stacked: ``w_gate`` and
    ``w_up`` (count, hidden, width), ``w_down`` (count, width, hidden), each
    (in, out); every expert's values come from its own key."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]

    def one(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1000 + e), 3)
        return {"w_gate": _normal(kg, (h, i), dtype, cfg),
                "w_up": _normal(ku, (h, i), dtype, cfg),
                "w_down": _normal(kd, (i, h), dtype, cfg)}

    return jax.vmap(one)(first + jnp.arange(count))


def init_layer(key, cfg, dtype, kind="experts", experts=True):
    """One layer's weights from its key; ``experts=False`` leaves the expert
    bank out (the forward pass makes it a group at a time)."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    w = {n: _normal(k, shapes[n], dtype, cfg)
         for k, n in zip(keys, sorted(shapes))}
    idim = _index(cfg)[1]
    w.update(attn_norm=jnp.ones((cfg["hidden_size"],), dtype),
             ffn_norm=jnp.ones((cfg["hidden_size"],), dtype),
             q_norm=jnp.ones((cfg["head_dim"],), dtype),
             k_norm=jnp.ones((cfg["head_dim"],), dtype),
             idx_k_norm=jnp.ones((idim,), dtype),
             idx_k_bias=jnp.zeros((idim,), dtype))
    if experts:
        w.update(init_experts(key, cfg, dtype, 0, cfg["num_experts"]))
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


def text_positions(t):
    """A text's position triples: every stream the token's index, (3, T)."""
    return jnp.broadcast_to(jnp.arange(t), (3, t))


def _rope_half(x, pos_by_freq, theta):
    """x (T, .., D) rotated in pairs (i, i + D/2): frequency ``i`` by
    ``pos_by_freq`` (T, D/2) * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d)),
                      jnp.float32)
    ang = pos_by_freq.astype(jnp.float32) * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


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


def attention_inputs(x, w, cfg, lowp, positions=None):
    """What every query block shares: ``q`` (T, heads, hd) and ``k`` / ``v``
    (T, kv heads, hd) after norm and rotation, the index queries (T, index
    heads, D), the index keys (T, D) and the index weights (T, index heads).
    ``positions`` (3, T): the tokens' triples (None: text)."""
    t, hd = x.shape[0], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    ih, idim, _ = _index(cfg)
    pos = text_positions(t) if positions is None else jnp.asarray(positions)
    stream = np.repeat(np.arange(3), cfg["rope_scaling"]["mrope_section"])
    assert len(stream) == hd // 2
    by_freq = pos[jnp.asarray(stream)].T                     # (T, hd/2)
    u = _rms(x, _f32(w["attn_norm"]), eps)
    q = _rms(_mm(u, _f32(w["q"]), lowp).reshape(t, -1, hd), _f32(w["q_norm"]),
             eps)
    k = _rms(_mm(u, _f32(w["k"]), lowp).reshape(t, -1, hd), _f32(w["k_norm"]),
             eps)
    v = _mm(u, _f32(w["v"]), lowp).reshape(t, -1, hd)
    q, k = _rope_half(q, by_freq, theta), _rope_half(k, by_freq, theta)
    p_t = jnp.broadcast_to(pos[0][:, None], (t, idim // 2))
    k_idx = _rope_half(
        _layer_norm(_mm(u, _f32(w["idx_k"]), lowp), _f32(w["idx_k_norm"]),
                    _f32(w["idx_k_bias"])), p_t, theta)
    q_idx = _rope_half(_mm(u, _f32(w["idx_q"]), lowp).reshape(t, ih, idim),
                       p_t, theta)
    w_idx = _mm(u, _f32(w["idx_w"]), lowp) * (ih ** -0.5 * idim ** -0.5)
    return q, k, v, q_idx, k_idx, w_idx


def _select_block(queries, keys, w_idx, cfg, select, blk, i, extent):
    """``S_t`` of the ``blk`` query rows of block ``i`` over the first
    ``extent`` keys -> (blk, extent) bool."""
    ih, _, topk = _index(cfg)
    k = min(topk, keys.shape[0])
    cols = jnp.arange(extent)
    rows = i * blk + jnp.arange(blk)
    visible = cols[None, :] <= rows[:, None]
    if select == "recent":
        return visible & (cols[None, :] > rows[:, None] - k)
    qb = jax.lax.dynamic_slice_in_dim(queries, i * blk, blk, axis=0)
    wi = jax.lax.dynamic_slice_in_dim(w_idx, i * blk, blk, axis=0)

    def head(acc, j):
        return acc + wi[:, j, None] * jax.nn.relu(
            qb[:, j] @ keys[:extent].T), None

    scores, _ = jax.lax.scan(head, jnp.zeros((blk, extent), jnp.float32),
                             jnp.arange(ih))
    scores = jnp.where(visible, scores, -jnp.inf)
    kk = min(k, extent)
    least = jax.lax.top_k(scores, kk)[0][:, -1:]
    above = scores > least
    tied = (scores == least) & visible
    room = kk - above.sum(axis=-1, keepdims=True)
    # of the positions that tie with the smallest taken, the earliest
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def selection(q_idx, k_idx, w_idx, cfg, lowp, select="indexer"):
    """``S_t`` of every row as packed bits: (T, ceil(T / 8)) uint8, bit ``s`` of
    row ``t`` (``numpy.packbits`` order) set where ``s`` is selected.  In blocks
    of query rows, the index heads one at a time."""
    t = q_idx.shape[0]
    blk = _blocks(t, Q_BLOCK)
    width = -(-t // 8)
    keys = _fp8(k_idx) if lowp else k_idx
    queries = _fp8(q_idx) if lowp else q_idx

    def block(i, extent):
        bits = jnp.packbits(_select_block(queries, keys, w_idx, cfg, select,
                                          blk, i, extent), axis=-1)
        return jnp.pad(bits, ((0, 0), (0, width - bits.shape[-1])))

    return _over_query_blocks(block, t, blk).reshape(t, width)


def selection_row(q_idx, k_idx, w_idx, cfg, lowp, select, row):
    """``S_t`` of the one row ``row`` (traced): :func:`selection`'s own block
    of query rows around it, over every key -> (T,) bool."""
    t = q_idx.shape[0]
    blk = _blocks(t, Q_BLOCK)
    keys = _fp8(k_idx) if lowp else k_idx
    queries = _fp8(q_idx) if lowp else q_idx
    return _select_block(queries, keys, w_idx, cfg, select, blk, row // blk,
                         t)[row % blk]


def attention(x, q, k, v, chosen_bits, w, cfg, lowp):
    """``x + attention``: the query heads of one key-value head at a time, each
    in blocks of query rows under the selection's mask."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    blk = _blocks(t, Q_BLOCK)
    w_o = w["o"].reshape(-1, nkv, g * hd).transpose(1, 0, 2)
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)

    def group(acc, part):
        qg, kg, vg, wo = part              # (T, g, hd), (T, hd), (T, hd)

        def block(i, extent):
            qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, axis=0)
            bits = jax.lax.dynamic_slice_in_dim(chosen_bits, i * blk, blk, axis=0)
            chosen = jnp.unpackbits(bits[:, :-(-extent // 8)], axis=-1,
                                    count=extent).astype(bool)
            s = jnp.einsum("qhd,td->hqt", qb, kg[:extent]) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
            if lowp:
                p = _fp8(p)
            return jnp.einsum("hqt,td->qhd", p, vg[:extent])

        ctx = _over_query_blocks(block, t, blk).reshape(t, g * hd)
        return acc + _mm(ctx, _f32(wo), lowp), None

    out, _ = jax.lax.scan(
        group, x, (q.reshape(t, nkv, g, hd).transpose(1, 0, 2, 3),
                   k.transpose(1, 0, 2), v.transpose(1, 0, 2), w_o))
    return out


def _swiglu(u, gate, up, down, lowp):
    g = _mm(u, gate, lowp)
    return _mm(jax.nn.silu(g) * _mm(u, up, lowp), down, lowp)


def combine_weights(u, w, cfg, lowp):
    """-> ((T, experts) float32: an expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in router logits,
    where rounding moves the choice)."""
    k = cfg["num_experts_per_tok"]
    logits = _mm(u, _f32(w["router"]), lowp)
    s = jax.nn.softmax(logits, axis=-1)
    lead, idx = jax.lax.top_k(logits, k + 1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].add(chosen), margin


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


def ffn_front(x, w, cfg, lowp):
    """-> (the normed rows, the experts' combine weights, the choice margins)."""
    u = _rms(x, _f32(w["ffn_norm"]), cfg["rms_norm_eps"])
    return (u,) + combine_weights(u, w, cfg, lowp)


def layer_forward(x, w, cfg, kind="experts", lowp=False, select="indexer",
                  positions=None):
    """One whole layer over one sequence from a full set of weights ->
    (x, the selection's packed bits, the choice margins)."""
    q, k, v, q_idx, k_idx, w_idx = attention_inputs(x, w, cfg, lowp, positions)
    bits = selection(q_idx, k_idx, w_idx, cfg, lowp, select)
    x = attention(x, q, k, v, bits, w, cfg, lowp)
    u, comb, margin = ffn_front(x, w, cfg, lowp)
    x = x + experts_part(u, comb, {n: w[n] for n in
                                   ("w_gate", "w_up", "w_down")}, lowp)
    return x, bits, margin


def forward(cfg, weights, ids, lowp=False, select="indexer", with_selection=False,
            positions=None):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}``; ``positions`` (3, T) its tokens' triples (None: text);
    ``with_selection`` adds (layers, T, T) bool.  One compiled program a
    configuration and length (the tests' sizes)."""
    ids = jnp.asarray(ids)
    pos = text_positions(ids.shape[0]) if positions is None \
        else jnp.asarray(positions)
    return _forward_program(json.dumps(cfg, sort_keys=True), bool(lowp), select,
                            bool(with_selection))(weights, ids, pos)


@functools.lru_cache(maxsize=None)
def _forward_program(cfg_json, lowp, select, with_selection):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda weights, ids, pos: _forward(
        cfg, weights, ids, pos, lowp, select, with_selection))


def _forward(cfg, weights, ids, positions, lowp, select, with_selection):
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["top"]["emb"][jnp.asarray(ids)])
        chosen = []
        for w in weights["layers"]:
            x, bits, _m = layer_forward(x, w, cfg, "experts", lowp, select,
                                        positions)
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
        return _f32(top["emb"][ids])

    @functools.partial(jax.jit, static_argnums=3)
    def select_fn(seed_key, l, x, select):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, experts=False)
        with jax.default_matmul_precision("highest"):
            q, k, v, q_idx, k_idx, w_idx = attention_inputs(x, w, cfg, lowp)
            return q, k, v, selection(q_idx, k_idx, w_idx, cfg, lowp, select)

    @functools.partial(jax.jit, static_argnums=3)
    def select_row_fn(seed_key, x, row, select):
        w = init_layer(layer_key(seed_key, 0), cfg, dtype, experts=False)
        with jax.default_matmul_precision("highest"):
            _q, _k, _v, q_idx, k_idx, w_idx = attention_inputs(x, w, cfg, lowp)
            return selection_row(q_idx, k_idx, w_idx, cfg, lowp, select, row)

    @functools.partial(jax.jit, donate_argnums=2)
    def attend_fn(seed_key, l, x, q, k, v, bits):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, experts=False)
        with jax.default_matmul_precision("highest"):
            x = attention(x, q, k, v, bits, w, cfg, lowp)
            return (x,) + ffn_front(x, w, cfg, lowp)

    @functools.partial(jax.jit, donate_argnums=5)
    def group(seed_key, l, first, u, comb, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype, first,
                            EXPERT_GROUP)
        part = jax.lax.dynamic_slice_in_dim(comb, first, EXPERT_GROUP, axis=1)
        with jax.default_matmul_precision("highest"):
            return acc + experts_part(u, part, bank, lowp)

    @jax.jit
    def logits(seed_key, picked):
        top = init_top(top_key(seed_key), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, _f32(top["norm"]), cfg["rms_norm_eps"])
            parts = _blocks(cfg["vocab_size"], VOCAB_CHUNKS)
            head = top["head"].reshape(parts, -1, cfg["hidden_size"])
            out = jax.lax.map(lambda part: _mm(h, _f32(part), lowp), head)
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)

    return embed, select_fn, attend_fn, group, logits, select_row_fn


def _cfg_json(cfg):
    keep = ("hidden_size", "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "rope_scaling", "sa_config",
            "vocab_size", "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, lowp=False, with_margin=False,
                 select="indexer", selected_at=None):
    """Logits of the full forward pass at chosen positions, weights remade from
    the seed: ``ids`` (N, T) int32, every sequence padded at its end; ``rows``
    (M, 2) pairs (sequence, position).  One sequence at a time, a layer's
    weights, and of its experts a group's, on the device at a time.  -> (M,
    vocab) float32 on the host; ``with_margin`` adds (M,): the smallest choice
    margin of the row's token over the layers; ``selected_at`` (N,) positions
    adds (layers, N, T) bool: what each layer selects for that row of each
    sequence."""
    embed, select_fn, attend_fn, group, logits, _row = _programs(
        _cfg_json(cfg), cfg["torch_dtype"], bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    experts = cfg["num_experts"]
    assert experts % EXPERT_GROUP == 0
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
            q, k, v, bits = select_fn(key, jnp.int32(l), x, select)
            if selected_at is not None:
                chosen[l, i] = np.unpackbits(
                    np.asarray(bits[int(selected_at[i])]), count=t).astype(bool)
            x, u, comb, m = attend_fn(key, jnp.int32(l), x, q, k, v, bits)
            del q, k, v, bits
            margin = jnp.minimum(margin, m)
            acc = jnp.zeros_like(x)
            for first in range(0, experts, EXPERT_GROUP):
                acc = group(key, jnp.int32(l), jnp.int32(first), u, comb, acc)
            x = x + acc
            del u, comb, acc
        at = jnp.asarray(np.where(mine, rows[:, 1], 0))
        picked = jnp.where(jnp.asarray(mine)[:, None], x[at], picked)
        margins = np.where(mine, np.asarray(margin[at]), margins)
        del x
    chunk = _blocks(len(rows), ROW_CHUNK)
    out = [np.concatenate([np.asarray(logits(key, picked[a:a + chunk]))
                           for a in range(0, len(rows), chunk)])]
    if with_margin:
        out.append(margins)
    if selected_at is not None:
        out.append(chosen)
    return out[0] if len(out) == 1 else tuple(out)


def first_layer_selected(cfg, seed, ids, at, lowp=False, select="indexer"):
    """What the FIRST layer selects at position ``at[i]`` of sequence ``ids[i]``
    (N, T; padded at its end), weights remade from the seed -> (N, T) bool.
    The first layer's indexer reads the embeddings alone: no attention and no
    expert stands before it, so this costs an embedding and three small
    products a sequence, and a program that serves from the same weights has
    nothing but its own rounding between its set and this one."""
    programs = _programs(_cfg_json(cfg), cfg["torch_dtype"], bool(lowp))
    embed, select_row_fn = programs[0], programs[-1]
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    ids = np.asarray(ids, np.int32)
    return np.stack([np.asarray(select_row_fn(
        key, embed(key, jnp.asarray(row)), jnp.int32(a), select))
        for row, a in zip(ids, at)])


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

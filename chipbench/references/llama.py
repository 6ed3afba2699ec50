"""Plain reference of the Llama/Mistral decoder: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernels, cache or batching.

It follows the published architecture (pre-norm decoder, RMSNorm, grouped-query
attention with rotary position embedding, SwiGLU feed-forward, untied output
head).  One departure, which seeded random weights cannot tell apart: the
rotation pairs dimensions (2i, 2i+1) of a head, as in the RoFormer paper and
Meta's release, where the Hugging Face port pairs (i, i + d/2); the two differ
by a fixed permutation of each head's query and key rows.

It imports nothing of the program.  The weights are made here from the seed
(``init_layer``, ``init_top``), in the type they are served in; the benchmark
hands the same values to the program (``families/llama.py``), never the other
way round.  ``lowp`` computes every matrix multiplication on operands rounded
to float8 (e4m3, one scale per tensor): the control of "How correct is
decided", the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
Q_BLOCK = 1024   # attention is computed in blocks of this many query rows


def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * INIT_STD).astype(dtype)


def layer_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (nq * hd, h), "k": (nkv * hd, h), "v": (nkv * hd, h),
            "o": (h, nq * hd), "gate": (f, h), "up": (f, h), "down": (h, f)}


def init_layer(key, cfg, dtype):
    """One decoder layer's weights from its key; matrices are (out, in)."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    w = {n: _normal(k, shapes[n], dtype)
         for k, n in zip(keys, sorted(shapes))}
    w["ln_in"] = jnp.ones((cfg["hidden_size"],), dtype)
    w["ln_post"] = jnp.ones((cfg["hidden_size"],), dtype)
    return w


def init_top(key, cfg, dtype):
    ke, kh = jax.random.split(key)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"emb": _normal(ke, (v, h), dtype), "head": _normal(kh, (v, h), dtype),
            "norm": jnp.ones((h,), dtype)}


def layer_key(seed_key, layer):
    return jax.random.fold_in(seed_key, layer + 1)


def top_key(seed_key):
    return jax.random.fold_in(seed_key, 0)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b_t, lowp):
    """a @ b_t.T in float32."""
    if lowp:
        a, b_t = _fp8(a), _fp8(b_t)
    return a @ b_t.T


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (T, H, D): rotate pairs (2i, 2i+1) by position * theta^(-2i/D)."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.outer(np.arange(t, dtype=np.float64), inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, lowp):
    """Causal softmax attention; q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, d = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    blk = max(b for b in range(1, min(Q_BLOCK, t) + 1) if t % b == 0)
    cols = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=0)
        s = jnp.einsum("qhd,thd->hqt", qb, k) / np.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqt,thd->qhd", p, v)

    return jax.lax.map(block, jnp.arange(t // blk)).reshape(t, hq, d)


def layer_forward(x, w, cfg, lowp=False):
    """One decoder layer over one sequence; x (T, hidden) float32."""
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    hd = cfg["head_dim"]
    t = x.shape[0]
    h = _rms(x, w["ln_in"], cfg["rms_norm_eps"])
    q = _mm(h, w["q"], lowp).reshape(t, -1, hd)
    k = _mm(h, w["k"], lowp).reshape(t, -1, hd)
    v = _mm(h, w["v"], lowp).reshape(t, -1, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    ctx = _attention(q, k, v, lowp).reshape(t, -1)
    x = x + _mm(ctx, w["o"], lowp)
    h = _rms(x, w["ln_post"], cfg["rms_norm_eps"])
    g = _mm(h, w["gate"], lowp)
    return x + _mm(jax.nn.silu(g) * _mm(h, w["up"], lowp), w["down"], lowp)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, dtype_name, lowp):
    cfg = dict(cfg_items)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return top["emb"].astype(jnp.float32)[ids]

    @jax.jit
    def layer(seed_key, l, xs):
        w = init_layer(layer_key(seed_key, l), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda x: layer_forward(x, w, cfg, lowp), xs)

    @jax.jit
    def logits(seed_key, xs, rows):
        top = init_top(top_key(seed_key), cfg, dtype)
        picked = xs[rows[:, 0], rows[:, 1]]
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, top["norm"].astype(jnp.float32), cfg["rms_norm_eps"])
            return _mm(h, top["head"].astype(jnp.float32), lowp)

    return embed, layer, logits


def _cfg_key(cfg):
    keep = ("hidden_size", "intermediate_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps",
            "num_hidden_layers")
    return tuple((k, cfg[k]) for k in keep)


def forward_rows(cfg, seed, ids, rows, lowp=False):
    """Logits of the full forward pass at chosen positions.

    ``ids`` (N, T) int32, every sequence padded at its end to T; ``rows`` (M, 2) int32 pairs (sequence,
    position).  Layer by layer, one layer's weights on the device at a time.
    Returns (M, vocab) float32 on the host."""
    embed, layer, logits = _programs(_cfg_key(cfg), cfg["torch_dtype"], bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    xs = embed(key, jnp.asarray(ids, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        xs = layer(key, jnp.int32(l), xs)
    rows = jnp.asarray(rows)
    return np.asarray(logits(key, xs, rows))


def served_gaps(cfg, seed, prompts, served, pad_to, max_rows, lowp_control=False):
    """The serving comparison.  For each request, ``prompts[i]`` then
    ``served[i]`` is run once through the reference; at each served token the
    gap is (reference's best logit) - (reference's logit of the served token),
    in units of that position's logit standard deviation.  With
    ``lowp_control`` the float8 reference takes the program's place: the gap
    is read for the token it puts first.  Returns the gaps, one per token."""
    n = len(prompts)
    ids = np.zeros((n, pad_to), np.int32)
    rows, toks = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, s])
        assert len(seq) <= pad_to
        ids[i, :len(seq)] = seq
        for j, tok in enumerate(s):
            rows.append((i, len(p) - 1 + j))   # the position that predicts s[j]
            toks.append(tok)
    n_rows = len(rows)
    assert n_rows <= max_rows
    # a fixed number of rows, so that every run of a cell compiles the same
    # shapes: the padding repeats the first row and is dropped again
    rows = np.asarray(rows + [rows[0]] * (max_rows - n_rows), np.int32)
    ref = forward_rows(cfg, seed, ids, rows)[:n_rows]
    if lowp_control:
        toks = forward_rows(cfg, seed, ids, rows, lowp=True)[:n_rows].argmax(axis=-1)
    toks = np.asarray(toks)
    best = ref.max(axis=-1)
    got = ref[np.arange(len(toks)), toks]
    return (best - got) / ref.std(axis=-1)

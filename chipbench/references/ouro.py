"""Plain reference of the Ouro looped decoder (``model_type`` ``ouro``): float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``; whole
sequences, no cache, no kernel, no batching.

The equations (the LoopLM report; the ``transformers`` port's names in
brackets), ``N_*`` an RMSNorm with a learned weight, no bias in a layer:

* layer ``l`` in pass ``t``: ``a = N_in(h)`` [input_layernorm]; ``q, k, v = a
  W_q, a W_k, a W_v`` (as many KV heads as query heads); rotary embedding over
  the whole head on q and k; ``o = softmax(q k^T / sqrt(hd)) v`` causally over
  THIS pass's keys and values of this layer (the port's cache index ``t x
  layers + l``); ``h = h + N_in2(o W_o)`` [input_layernorm_2]; ``m = N_post(h)``
  [post_attention_layernorm]; ``h = h + N_post2((silu(m W_gate) * (m W_up))
  W_down)`` [post_attention_layernorm_2];
* model: ``h = E[ids]``; ``total_ut_steps`` times: ``h`` through every layer
  (the same weights every pass), then ``h = N_final(h)``.  Logits: the untied
  head over the last pass's output.
* the exit gate (hidden -> 1 with a bias; ``gate_w``, ``gate_b`` of
  ``init_top``) is made and never read: at ``early_exit_threshold`` 1, as
  published, no token leaves the loop before the last pass.

One departure, which seeded random weights cannot tell apart: the rotation
pairs dimensions (2i, 2i+1) of a head, where the port pairs (i, i + d/2).

It imports nothing of the program.  The weights are made here from the seed
(``init_layer``, ``init_top``), in the type they are served in, a layer at a
time, so that the ``passes x layers`` applications of the check fit beside the
served model; the benchmark hands the same values to the program
(``families/ouro.py``), never the other way round.

``fault`` puts a wrong forward in the reference's place, for the controls of
"How correct is decided" and for the tests' planted faults:

* ``"lowp"``: every matrix product on operands rounded to float8 (e4m3, one
  scale a tensor): the step below the bfloat16 the configuration states;
* ``"shared_cache"``: pass ``t`` > 0 attends the keys and values of pass ``t -
  1`` of the same layer (a server whose passes address one another's rows);
* ``"passes_short"``: one pass fewer; ``"norm_once"``: the final norm after
  the last pass only; ``"no_post_norms"``: ``N_in2`` and ``N_post2`` left out
  (a pre-norm layer); ``"bfloat16"``: every product on operands rounded to
  bfloat16 and rounded to bfloat16 again (for a float32 configuration).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024    # attention is computed in blocks of this many query rows
ROW_BLOCK = 1024  # and the logits in blocks of this many rows
FAULTS = (None, "lowp", "shared_cache", "passes_short", "norm_once",
          "no_post_norms", "bfloat16")
NORMS = ("ln_in", "ln_in2", "ln_post", "ln_post2")


def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (nq * hd, h), "k": (nkv * hd, h), "v": (nkv * hd, h),
            "o": (h, nq * hd), "gate": (f, h), "up": (f, h), "down": (h, f)}


def init_layer(key, cfg, dtype):
    """One layer's weights from its key; matrices are (out, in)."""
    shapes = layer_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    w = {n: _normal(k, shapes[n], dtype, cfg["initializer_range"])
         for k, n in zip(keys, sorted(shapes))}
    for n in NORMS:
        w[n] = jnp.ones((cfg["hidden_size"],), dtype)
    return w


def init_top(key, cfg, dtype):
    ke, kh, kg = jax.random.split(key, 3)
    v, h, std = cfg["vocab_size"], cfg["hidden_size"], cfg["initializer_range"]
    return {"emb": _normal(ke, (v, h), dtype, std),
            "head": _normal(kh, (v, h), dtype, std),
            "norm": jnp.ones((h,), dtype),
            "gate_w": _normal(kg, (1, h), dtype, std),
            "gate_b": jnp.zeros((1,), dtype)}


def layer_key(seed_key, layer):
    return jax.random.fold_in(seed_key, layer + 1)


def top_key(seed_key):
    return jax.random.fold_in(seed_key, 0)


def passes_of(cfg, fault=None):
    return int(cfg["total_ut_steps"]) - (fault == "passes_short")


def _f32(a):
    return a.astype(jnp.float32)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b_t, fault):
    """a @ b_t.T in float32."""
    if fault == "lowp":
        a, b_t = _fp8(a), _fp8(b_t)
    if fault == "bfloat16":
        return _bf16(_bf16(a) @ _bf16(b_t).T)
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


def _attention(q, k, v, fault):
    """Causal softmax attention; q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, d = q.shape
    rep = hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if fault == "lowp":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    if fault == "bfloat16":
        q, k, v = _bf16(q), _bf16(k), _bf16(v)
    blk = max(b for b in range(1, min(Q_BLOCK, t) + 1) if t % b == 0)
    cols = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=0)
        s = jnp.einsum("qhd,thd->hqt", qb, k) / np.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fault == "lowp":
            p = _fp8(p)
        if fault == "bfloat16":
            p = _bf16(p)
        return jnp.einsum("hqt,thd->qhd", p, v)

    return jax.lax.map(block, jnp.arange(t // blk)).reshape(t, hq, d)


def layer_forward(x, w, cfg, fault=None, kv_before=None):
    """One layer over one sequence in one pass; ``x`` (T, hidden) float32 ->
    (x, this pass's (k, v) after the rotation).  ``kv_before``: the pass
    before's (k, v) of this layer, which ``"shared_cache"`` attends instead."""
    w = {n: _f32(a) for n, a in w.items()}
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    a = _rms(x, w["ln_in"], eps)
    q = _mm(a, w["q"], fault).reshape(t, -1, hd)
    k = _mm(a, w["k"], fault).reshape(t, -1, hd)
    v = _mm(a, w["v"], fault).reshape(t, -1, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    ka, va = (k, v) if kv_before is None else kv_before
    y = _mm(_attention(q, ka, va, fault).reshape(t, -1), w["o"], fault)
    post = fault != "no_post_norms"
    x = x + (_rms(y, w["ln_in2"], eps) if post else y)
    m = _rms(x, w["ln_post"], eps)
    y = _mm(jax.nn.silu(_mm(m, w["gate"], fault)) * _mm(m, w["up"], fault),
            w["down"], fault)
    return x + (_rms(y, w["ln_post2"], eps) if post else y), (k, v)


def forward(cfg, weights, ids, fault=None):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}`` (the tests' sizes)."""
    assert fault in FAULTS
    top = {n: _f32(a) for n, a in weights["top"].items()}
    passes = passes_of(cfg, fault)
    with jax.default_matmul_precision("highest"):
        x = top["emb"][jnp.asarray(ids)]
        kv = [None] * len(weights["layers"])
        for t in range(passes):
            for l, w in enumerate(weights["layers"]):
                x, kv[l] = layer_forward(
                    x, w, cfg, fault,
                    kv[l] if fault == "shared_cache" and t else None)
            if fault != "norm_once" or t == passes - 1:
                x = _rms(x, top["norm"], cfg["rms_norm_eps"])
        return _mm(x, top["head"], fault)


# -- the forward pass of the check, a layer's weights at a time ------------------

@functools.lru_cache(maxsize=None)
def _programs(cfg_json, dtype_name, fault):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return _f32(top["emb"])[ids]

    @jax.jit
    def layer(seed_key, l, xs):
        w = init_layer(layer_key(seed_key, l), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda x: layer_forward(x, w, cfg, fault)[0], xs)

    @jax.jit
    def layer_kv(seed_key, l, xs, kv_before):
        # "shared_cache": this pass's keys and values go out, the pass
        # before's come in (None in pass 0)
        w = init_layer(layer_key(seed_key, l), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            if kv_before is None:
                return jax.lax.map(
                    lambda x: layer_forward(x, w, cfg, fault), xs)
            return jax.lax.map(
                lambda a: layer_forward(a[0], w, cfg, fault, a[1]),
                (xs, kv_before))

    @jax.jit
    def end_pass(seed_key, xs):
        top = init_top(top_key(seed_key), cfg, dtype)
        return _rms(xs, _f32(top["norm"]), cfg["rms_norm_eps"])

    @jax.jit
    def logits(seed_key, xs, rows):
        top = init_top(top_key(seed_key), cfg, dtype)
        with jax.default_matmul_precision("highest"):
            return _mm(xs[rows[:, 0], rows[:, 1]], _f32(top["head"]), fault)

    return embed, layer, layer_kv, end_pass, logits


def _cfg_json(cfg):
    keep = ("hidden_size", "intermediate_size", "head_dim",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rope_theta", "rms_norm_eps", "num_hidden_layers",
            "total_ut_steps", "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, fault=None):
    """Logits of the full forward pass at chosen positions, weights remade
    from the seed.

    ``ids`` (N, T) int32, every sequence padded at its end to T; ``rows`` (M,
    2) int32 pairs (sequence, position).  Pass by pass and layer by layer, one
    layer's weights on the device at a time; under ``"shared_cache"`` a pass's
    keys and values wait for the next pass on the host.  Returns (M, vocab)
    float32 on the host."""
    assert fault in FAULTS
    embed, layer, layer_kv, end_pass, logits = _programs(
        _cfg_json(cfg), cfg["torch_dtype"], fault)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    xs = embed(key, jnp.asarray(ids, jnp.int32))
    layers = cfg["num_hidden_layers"]
    kv = [None] * layers
    for t in range(passes_of(cfg, fault)):
        for l in range(layers):
            if fault == "shared_cache":
                xs, new = layer_kv(key, jnp.int32(l), xs, kv[l])
                kv[l] = jax.tree_util.tree_map(np.asarray, new)
            else:
                xs = layer(key, jnp.int32(l), xs)
        xs = end_pass(key, xs)
    rows = np.asarray(rows, np.int32)
    padded = np.concatenate([rows, np.repeat(rows[:1], -len(rows) % ROW_BLOCK,
                                             axis=0)])
    out = [np.asarray(logits(key, xs, jnp.asarray(block)))
           for block in padded.reshape(-1, ROW_BLOCK, 2)]
    return np.concatenate(out)[:len(rows)]


def served_gaps(cfg, seed, prompts, served, pad_to, max_rows, control=None):
    """The serving comparison.  For each request, ``prompts[i]`` then
    ``served[i]`` is run once through the reference; at each served token the
    gap is (reference's best logit) - (reference's logit of the served token),
    in units of that position's logit standard deviation.  With ``control``
    (a ``fault`` of this file's) the faulty reference takes the program's
    place: the gap is read for the token IT puts first.  Returns the gaps, one
    per token."""
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
    if control is not None:
        toks = forward_rows(cfg, seed, ids, rows,
                            fault=control)[:n_rows].argmax(axis=-1)
    toks = np.asarray(toks)
    best = ref.max(axis=-1)
    got = ref[np.arange(len(toks)), toks]
    return (best - got) / ref.std(axis=-1)

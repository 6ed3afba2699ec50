"""Plain reference of BERT pretraining: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; forward, loss, gradient and Adam.

It follows Devlin et al. (2018) and google-research/bert ``modeling.py``:
token + segment + position embeddings, LayerNorm, post-norm encoder layers
with erf-GELU, and the masked-LM head (dense, GELU, LayerNorm, vocabulary
projection).  Departures, each stated in the configuration file under
``assumed``: the loss is cross-entropy over every position (no 15% masking, no
next-sentence loss), the vocabulary projection is a matrix of its own (the
program does not tie it to the embedding), and dropout falls where the
program's model applies it (hidden states only), with masks drawn here: the
program's cannot be known, so the two sides differ by mask noise.  LayerNorm's
epsilon is the published 1e-12 (the program's is 1e-5; at unit variance the
two differ by 5e-6, far under bfloat16's rounding).

It imports nothing of the program.  The parameters are made here from the seed
(``init_params``); the benchmark hands the same values to the program
(``families/bert.py``).  ``lowp`` rounds every matrix multiplication's
operands to float8 (e4m3, one scale per tensor): the control, one step below
the bfloat16 the configuration states (under dropout it reads within mask
noise of the float32 reference and fails no limit; PERF.md section 4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
ROWS_PER_BLOCK = 64     # gradients are summed over blocks of this many rows
BETA1, BETA2, EPS = 0.9, 0.999, 1e-8


def init_params(key, cfg):
    """The parameter tree from one key; matrices are (out, in), float32."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    names = []

    def dense(prefix, out, inn):
        names.append((prefix + ".w", (out, inn)))
        names.append((prefix + ".b", (out,)))

    def norm(prefix):
        names.append((prefix + ".g", (h,)))
        names.append((prefix + ".b", (h,)))

    names.append(("word", (v, h)))
    names.append(("type", (cfg["type_vocab_size"], h)))
    names.append(("pos", (cfg["max_position_embeddings"], h)))
    norm("emb_ln")
    for l in range(cfg["num_hidden_layers"]):
        for n in ("q", "k", "v", "o"):
            dense(f"l{l}.{n}", h, h)
        norm(f"l{l}.ln_att")
        dense(f"l{l}.ffn1", f, h)
        dense(f"l{l}.ffn2", h, f)
        norm(f"l{l}.ln_ffn")
    dense("pooler", h, h)
    dense("nsp", 2, h)
    dense("mlm.dense", h, h)
    norm("mlm.ln")
    dense("mlm.out", v, h)
    keys = jax.random.split(key, len(names))
    params = {}
    for k, (name, shape) in zip(keys, names):
        if name.endswith(".g"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(".b"):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jax.random.normal(k, shape, jnp.float32) * INIT_STD
    return params


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)    # backward sees the rounded operand's path


def _dense(p, prefix, x, lowp):
    w = p[prefix + ".w"]
    if lowp:
        x, w = _fp8(x), _fp8(w)
    return x @ w.T + p[prefix + ".b"]


def _ln(p, prefix, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p[prefix + ".g"] + p[prefix + ".b"]


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


def _drop(x, key, rate):
    """Inverted dropout with the reference's own mask; ``key`` None is none."""
    if key is None or not rate:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


LAYER_LEAVES = [f"{n}.{k}" for n in ("q", "k", "v", "o", "ffn1", "ffn2")
                for k in ("w", "b")] + ["ln_att.g", "ln_att.b", "ln_ffn.g", "ln_ffn.b"]


def encoder_layer(x, lp, cfg, lowp, key=None):
    """One post-norm encoder layer; ``lp`` holds this layer's leaves."""
    b, t, _ = x.shape
    nh = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    rate = cfg["hidden_dropout_prob"]
    k_att, k_ffn = (None, None) if key is None else jax.random.split(key)

    def heads(name):
        return _dense(lp, name, x, lowp).reshape(b, t, nh, -1)

    q, k, v = heads("q"), heads("k"), heads("v")
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    a = jax.nn.softmax(s, axis=-1)
    if lowp:
        a = _fp8(a)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, -1)
    x = _ln(lp, "ln_att", x + _drop(_dense(lp, "o", ctx, lowp), k_att, rate), eps)
    hdn = _gelu(_dense(lp, "ffn1", x, lowp))
    return _ln(lp, "ln_ffn", x + _drop(_dense(lp, "ffn2", hdn, lowp), k_ffn, rate), eps)


def mlm_loss(p, ids, seg, labels, cfg, lowp=False, key=None):
    """Sum over the rows given of the token cross-entropy of the masked-LM
    head, every position counted.  ids/seg/labels (B, T) int32.  The layers
    are alike, so they are stacked and run as a ``lax.scan``: the same
    arithmetic as a loop, in a program a twelfth the size.  ``key`` draws the
    dropout masks (after the embedding's LayerNorm, the attention's output
    projection and the second feed-forward matrix, where the program's model
    drops); None trains without dropout."""
    t = ids.shape[1]
    eps = cfg["layer_norm_eps"]
    n_layers = cfg["num_hidden_layers"]
    k_emb, k_layers = (None, None) if key is None else jax.random.split(key)
    x = p["word"][ids] + p["type"][seg] + p["pos"][:t][None]
    x = _drop(_ln(p, "emb_ln", x, eps), k_emb, cfg["hidden_dropout_prob"])
    stacked = {n: jnp.stack([p[f"l{l}.{n}"] for l in range(n_layers)])
               for n in LAYER_LEAVES}
    if key is None:
        x, _ = jax.lax.scan(lambda h, lp: (encoder_layer(h, lp, cfg, lowp), None),
                            x, stacked)
    else:
        x, _ = jax.lax.scan(
            lambda h, lk: (encoder_layer(h, lk[0], cfg, lowp, lk[1]), None),
            x, (stacked, jax.random.split(k_layers, n_layers)))
    hm = _ln(p, "mlm.ln", _gelu(_dense(p, "mlm.dense", x, lowp)), eps)
    logits = _dense(p, "mlm.out", hm, lowp)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - picked).sum()


def adam(p, m, v, g, lr, t):
    """Adam as in Kingma & Ba, section 2's closing variant (epsilon-hat)."""
    m = jax.tree.map(lambda m_, g_: BETA1 * m_ + (1 - BETA1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: BETA2 * v_ + (1 - BETA2) * g_ * g_, v, g)
    lr_t = lr * jnp.sqrt(1 - BETA2 ** t) / (1 - BETA1 ** t)
    p = jax.tree.map(lambda p_, m_, v_: p_ - lr_t * m_ / (jnp.sqrt(v_) + EPS),
                     p, m, v)
    return p, m, v


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, lowp):
    cfg = dict(cfg_items)

    @jax.jit
    def block_grad(p, ids, seg, labels, key):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(mlm_loss)(
                p, ids, seg, labels, cfg, lowp,
                key if cfg["hidden_dropout_prob"] else None)

    @jax.jit
    def update(p, m, v, g, lr, t):
        return adam(p, m, v, g, lr, t)

    @jax.jit
    def start(key):
        p = init_params(key, cfg)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return p, zeros, zeros

    @jax.jit
    def norms(tree):
        return {n: jnp.sqrt(jnp.sum(a * a)) for n, a in tree.items()}

    @jax.jit
    def delta_norms(a, b):
        return {n: jnp.sqrt(jnp.sum((a[n] - b[n]) ** 2)) for n in a}

    @jax.jit
    def root_sums(tree):
        return {n: jnp.sqrt(jnp.sum(a)) for n, a in tree.items()}

    return start, block_grad, update, norms, delta_norms, root_sums


def _cfg_key(cfg):
    keep = ("hidden_size", "intermediate_size", "vocab_size", "type_vocab_size",
            "max_position_embeddings", "num_hidden_layers",
            "num_attention_heads", "layer_norm_eps", "hidden_dropout_prob")
    return tuple((k, cfg[k]) for k in keep)


def follow(cfg, seed, batches, lrs, lowp=False, masks=0):
    """Train from the seed's parameters over ``batches`` (each a tuple
    (ids, seg, labels) of (B, T) host arrays) at the learning rates ``lrs``
    (one a step), the loss being the mean token cross-entropy of the batch.  Returns the loss of every step, the norm of
    each leaf of Adam's first moment after the last step, and the norm of each
    leaf of the parameters' change: what the program's state is held to."""
    start, block_grad, update, norms, delta_norms, root_sums = _programs(_cfg_key(cfg), bool(lowp))
    p0, m, v = start(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    p = p0
    losses = []
    # the reference's own dropout masks: stream ``masks`` of the seed
    mask_key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                                  1000 + masks)
    for t, ((ids, seg, labels), lr) in enumerate(zip(batches, lrs), start=1):
        n_tok = ids.shape[0] * ids.shape[1]
        total, grad = 0.0, None
        for r in range(0, ids.shape[0], ROWS_PER_BLOCK):
            sl = slice(r, r + ROWS_PER_BLOCK)
            loss, g = block_grad(p, jnp.asarray(ids[sl]), jnp.asarray(seg[sl]),
                                 jnp.asarray(labels[sl]),
                                 jax.random.fold_in(mask_key, t * 4096 + r))
            total += float(loss)
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        grad = jax.tree.map(lambda a: a / n_tok, grad)
        losses.append(total / n_tok)
        p, m, v = update(p, m, v, grad, jnp.float32(lr), jnp.float32(t))
    return {"loss": losses,
            "moment_norm": {n: float(x) for n, x in norms(m).items()},
            "second_moment_root": {n: float(x) for n, x in root_sums(v).items()},
            "delta_norm": {n: float(x) for n, x in delta_norms(p, p0).items()}}

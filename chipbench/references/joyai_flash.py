"""Plain reference of JoyAI-LLM-Flash pre-training (the DeepSeek-V3 block
with its multi-token-prediction module): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; forward, both losses, gradients
by ``jax.grad``, AdamW and the choice bias's rule, written out.

It imports nothing of the program.  Equations (``norm`` an RMSNorm with a
learned weight, rotary pairs (2i, 2i+1), no biases), one row of the batch at a
time, gradients and expert counts summed over the rows:

* latent attention: ``c_q = norm(u W_qa)``, ``q = c_q W_qb`` (heads of nope +
  rope, RoPE on the rope part); ``[c_kv | k_r] = u W_kva``, ``c_kv = norm(
  c_kv)``, ``k_r = RoPE(k_r)``; ``[k_nope_h | v_h] = c_kv W_kvb``; dense causal
  scores over the expanded heads, ``/ sqrt(nope + rope)``, softmax, ``W_o``;
* an expert layer: ``s = sigmoid(m W_r^T)`` over all the router's experts, the
  8 largest of ``s + b``, weights the chosen ``s`` over their sum (+1e-6)
  times ``routed_scaling_factor``; the sum over the HELD experts alone, each
  computed in a loop over the bank on the rows that chose it (the other chips'
  share is left out, as in the program); plus the shared expert;
* the module: ``h'_i = W_eh [norm_e(E[t_{i+1}]) ; norm_h(h_L,i)]`` for ``i + 2 <
  T``, one expert layer, ``norm_f'``, the SAME ``E`` and ``W_head``;
* ``L = mean CE(logits_i, t_{i+1}) + mtp_loss_weight * mean CE(logits'_i,
  t_{i+2})``; the balance loss of the family's report is applied by neither
  side;
* AdamW (decoupled decay on the matrices, none on the norms' weights) and
  ``b_e <- b_e + speed * sign(mean(c) - c_e)`` from the step's rows by expert.

``lowp`` rounds every matrix product's operands to float8 (e4m3, one scale a
tensor): the control one step below the bfloat16 the configuration states.
``mtp=False`` leaves the extra prediction's term out: the second control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HEADS_PER_BLOCK = 8    # attention's (T, T) scores are held for this many heads


def _dims(cfg):
    return dict(
        h=cfg["hidden_size"], f=cfg["intermediate_size"],
        i=cfg["moe_intermediate_size"], nh=cfg["num_attention_heads"],
        ql=cfg["q_lora_rank"], kl=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], e=cfg["router_experts"],
        first=cfg["experts_held"][0], held=cfg["experts_held"][1],
        k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        shared=cfg["n_shared_experts"])


def layer_shapes(cfg, dense):
    d = _dims(cfg)
    h, nh, dn, dr, dv = d["h"], d["nh"], d["dn"], d["dr"], d["dv"]
    out = {"attn_norm": (h,), "ffn_norm": (h,), "q_a": (d["ql"], h),
           "q_a_norm": (d["ql"],), "q_b": (nh * (dn + dr), d["ql"]),
           "kv_a": (d["kl"] + dr, h), "kv_a_norm": (d["kl"],),
           "kv_b": (nh * (dn + dv), d["kl"]), "o": (h, nh * dv)}
    if dense:
        out.update(gate=(d["f"], h), up=(d["f"], h), down=(h, d["f"]))
    else:
        s = d["shared"] * d["i"]
        out.update(router=(d["e"], h), w_gate=(d["held"], h, d["i"]),
                   w_up=(d["held"], h, d["i"]), w_down=(d["held"], d["i"], h),
                   shared_gate=(s, h), shared_up=(s, h), shared_down=(h, s))
    return out


def leaf_shapes(cfg):
    """Every trained leaf, name -> shape: ``embed``, ``head``, ``norm``,
    ``l<n>.<leaf>``, ``mtp.<leaf>`` and the module's layer ``mtp.l.<leaf>``."""
    d = _dims(cfg)
    out = {"embed": (d["v"], d["h"]), "head": (d["v"], d["h"]),
           "norm": (d["h"],)}
    for l in range(d["layers"]):
        for n, s in layer_shapes(cfg, l < d["dense"]).items():
            out[f"l{l}.{n}"] = s
    out.update({"mtp.e_norm": (d["h"],), "mtp.h_norm": (d["h"],),
                "mtp.eh_proj": (d["h"], 2 * d["h"]), "mtp.norm": (d["h"],)})
    for n, s in layer_shapes(cfg, False).items():
        out[f"mtp.l.{n}"] = s
    return out


def expert_layers(cfg):
    """The prefixes of the layers that route, in the order of ``expert_rows``
    and of the bias: the stack's, then the module's."""
    d = _dims(cfg)
    return [f"l{l}" for l in range(d["dense"], d["layers"])] + ["mtp.l"]


def init_params(key, cfg):
    """The parameter tree from one key, float32: Normal(0, initializer_range)
    matrices, an expert's from the key of its index among the router's
    experts (so any share of a layer holds the same values), norms ones."""
    std = cfg["initializer_range"]
    first = cfg["experts_held"][0]
    shapes = leaf_shapes(cfg)
    params = {}
    for name, k in zip(shapes, jax.random.split(key, len(shapes))):
        shape = shapes[name]
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif len(shape) == 3:
            one = lambda e: jax.random.normal(  # noqa: E731
                jax.random.fold_in(k, e), shape[1:], jnp.float32) * std
            params[name] = jax.vmap(one)(first + jnp.arange(shape[0]))
        else:
            params[name] = jax.random.normal(k, shape, jnp.float32) * std
    return params


def decays(name):
    """Whether AdamW's decay applies to a leaf: every matrix, no norm."""
    return not name.endswith("norm")


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, lowp):
    """``x @ w.T`` for ``w`` (out, in)."""
    if lowp:
        x, w = _fp8(x), _fp8(w)
    return x @ w.T


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (T, ..., D), pairs (2i, 2i+1), position = row."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    shape = (t,) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _swiglu(u, gate, up, down, lowp):
    g = _mm(u, gate, lowp)
    return _mm(g * jax.nn.sigmoid(g) * _mm(u, up, lowp), down, lowp)


def attention(lp, u, cfg, lowp):
    """u (T, H) -> (T, H): dense causal attention over the expanded heads."""
    d = _dims(cfg)
    nh, dn, dr, dv, kl = d["nh"], d["dn"], d["dr"], d["dv"], d["kl"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = u.shape[0]
    c_q = _rms(_mm(u, lp["q_a"], lowp), lp["q_a_norm"], eps)
    q = _mm(c_q, lp["q_b"], lowp).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kv = _mm(u, lp["kv_a"], lowp)
    c_kv = _rms(kv[:, :kl], lp["kv_a_norm"], eps)
    k_r = _rope(kv[:, kl:], theta)
    kvb = _mm(c_kv, lp["kv_b"], lowp).reshape(t, nh, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_r[:, None], (t, nh, dr))], -1)
    v = kvb[..., dn:]
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv                      # (hb, T, .)
        if lowp:
            qb, kb, vb = _fp8(qb), _fp8(kb), _fp8(vb)
        s = jnp.einsum("hqd,hkd->hqk", qb, kb) * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        if lowp:
            p = _fp8(p)
        return jnp.einsum("hqk,hkd->hqd", p, vb)

    hb = HEADS_PER_BLOCK if nh % HEADS_PER_BLOCK == 0 else nh
    split = lambda a: a.transpose(1, 0, 2).reshape(  # noqa: E731
        nh // hb, hb, t, a.shape[-1])
    heads = jax.lax.map(block, (split(q), split(k), split(v)))
    heads = heads.reshape(nh, t, dv).transpose(1, 0, 2).reshape(t, nh * dv)
    return _mm(heads, lp["o"], lowp)


def route(lp, m, bias, cfg):
    """-> (idx (T, k), weights (T, k)) over all the router's experts."""
    d = _dims(cfg)
    s = jax.nn.sigmoid(m @ lp["router"].T)
    _, idx = jax.lax.top_k(s + bias, d["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


def routed(lp, m, bias, cfg, lowp, held=None):
    """The held experts' part of the routed sum, each expert of the bank in
    turn on the rows that chose it, and the rows every expert of the router
    received -> ((T, H), (E,) float32).  ``held`` (first, count) names
    another share of a bank that holds them all (the tests)."""
    d = _dims(cfg)
    first, count = held or (d["first"], d["held"])
    idx, w = route(lp, m, bias, cfg)
    counts = jnp.zeros((d["e"],), jnp.float32).at[idx].add(1.0)

    @jax.checkpoint
    def one(acc, ex):
        e, gate, up, down = ex
        c = jnp.where(idx == e, w, 0.0).sum(-1)          # (T,)
        y = _swiglu(m, gate.T, up.T, down.T, lowp)
        return acc + c[:, None] * y, None

    off = first - d["first"]
    bank = tuple(lp[n][off:off + count] for n in ("w_gate", "w_up", "w_down"))
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (first + jnp.arange(count),) + bank)
    return y, counts


def layer(lp, x, bias, cfg, lowp):
    """x (T, H) -> (x, rows by expert or None)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(lp, _rms(x, lp["attn_norm"], eps), cfg, lowp)
    m = _rms(x, lp["ffn_norm"], eps)
    if "router" not in lp:
        return x + _swiglu(m, lp["gate"], lp["up"], lp["down"], lowp), None
    y, counts = routed(lp, m, bias, cfg, lowp)
    y = y + _swiglu(m, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
                    lowp)
    return x + y, counts


def _sub(p, prefix):
    return {n[len(prefix) + 1:]: a for n, a in p.items()
            if n.startswith(prefix + ".")}


def _ce_sum(hidden, norm_w, head, labels, eps, lowp):
    logits = _mm(_rms(hidden, norm_w, eps), head, lowp)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - picked).sum(), logits


def row_losses(p, bias, ids, cfg, lowp=False):
    """One row ``ids`` (T,) -> (sum of the main cross-entropies over T - 1
    positions, sum of the module's over T - 2, rows by expert (expert layers,
    E), the main logits (T, V))."""
    d = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    x = p["embed"][ids]
    rows = []
    for l in range(d["layers"]):
        lp = _sub(p, f"l{l}")
        b = None if l < d["dense"] else bias[l - d["dense"]]
        x, c = jax.checkpoint(
            lambda lp, x, b: layer(lp, x, b, cfg, lowp))(lp, x, b)
        if c is not None:
            rows.append(c)
    main, logits = _ce_sum(x[:-1], p["norm"], p["head"], ids[1:], eps, lowp)
    both = jnp.concatenate(
        [_rms(p["embed"][ids[1:-1]], p["mtp.e_norm"], eps),
         _rms(x[:-2], p["mtp.h_norm"], eps)], axis=-1)
    x2, c = jax.checkpoint(lambda lp, x, b: layer(lp, x, b, cfg, lowp))(
        _sub(p, "mtp.l"), _mm(both, p["mtp.eh_proj"], lowp), bias[-1])
    rows.append(c)
    extra, _ = _ce_sum(x2, p["mtp.norm"], p["head"], ids[2:], eps, lowp)
    return main, extra, jnp.stack(rows), logits


def objective(p, bias, ids, cfg, n_rows, lowp=False, mtp_weight=None):
    """One row's share of the batch's objective, and what it read.
    ``mtp_weight``: the extra prediction's weight (default: the
    configuration's; it may be traced, so one program serves the sound run
    and the control without the term)."""
    t = ids.shape[0]
    if mtp_weight is None:
        mtp_weight = cfg["assumed_values"]["mtp_loss_weight"]
    main, extra, rows, _ = row_losses(p, bias, ids, cfg, lowp)
    main = main / (n_rows * (t - 1))
    extra = extra / (n_rows * (t - 2))
    return main + mtp_weight * extra, (main, extra, rows)


def adamw(p, m, v, g, lr, t, opt):
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"]
    m = {n: b1 * m[n] + (1 - b1) * g[n] for n in p}
    v = {n: b2 * v[n] + (1 - b2) * g[n] * g[n] for n in p}
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    p = {n: p[n] - lr_t * m[n] / (jnp.sqrt(v[n]) + eps)
         - (lr * wd * p[n] if decays(n) else 0.0) for n in p}
    return p, m, v


def bias_step(bias, rows, speed):
    """(expert layers, E): ``b + speed * sign(mean(c) - c)`` a layer."""
    return bias + speed * jnp.sign(rows.mean(-1, keepdims=True) - rows)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, lowp):
    import json

    cfg = json.loads(cfg_key)
    opt = cfg["assumed_values"]["optimizer"]

    @jax.jit
    def row_grad(p, bias, ids, n_rows, mtp_weight):
        with jax.default_matmul_precision("highest"):
            return jax.grad(
                lambda p: objective(p, bias, ids, cfg, n_rows, lowp,
                                    mtp_weight),
                has_aux=True)(p)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, g, lr, t):
        return adamw(p, m, v, g, lr, t, opt)

    @jax.jit
    def start(key):
        return init_params(key, cfg)

    @jax.jit
    def norms(tree):
        return {n: jnp.sqrt(jnp.sum(a * a)) for n, a in tree.items()}

    @jax.jit
    def delta_norms(a, key):
        b = init_params(key, cfg)
        return {n: jnp.sqrt(jnp.sum((a[n] - b[n]) ** 2)) for n in a}

    @jax.jit
    def root_sums(tree):
        return {n: jnp.sqrt(jnp.sum(a)) for n, a in tree.items()}

    return start, row_grad, add, update, norms, delta_norms, root_sums


def follow(cfg, seed, batches, lrs, lowp=False, mtp=True):
    """Train from the seed's parameters over ``batches`` (each (B, T) host
    ids) at the rates ``lrs``: the two loss terms and the rows by expert of
    every step, the norm of each leaf of Adam's first moment, the root of the
    sum of the second and the norm of each leaf's change after the last, and
    the choice bias after the last.  A row at a time; the moments wait on the
    host while a step's gradients are taken, so that the whole fits beside
    its own parameters."""
    import json

    start, row_grad, add, update, norms, delta_norms, root_sums = _programs(
        json.dumps(cfg, sort_keys=True), bool(lowp))
    weight = jnp.float32(
        cfg["assumed_values"]["mtp_loss_weight"] if mtp else 0.0)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    p = start(key)
    n_layers = len(expert_layers(cfg))
    bias = jnp.zeros((n_layers, cfg["router_experts"]), jnp.float32)
    speed = cfg["assumed_values"]["bias_update_speed"]
    m = v = None
    out = {"loss_main": [], "loss_mtp": [], "expert_rows": []}
    for t, (ids, lr) in enumerate(zip(batches, lrs), start=1):
        grad, main, extra, rows = None, 0.0, 0.0, 0.0
        n_rows = ids.shape[0]
        for r in range(n_rows):
            g, (a, b, c) = row_grad(p, bias, jnp.asarray(ids[r]),
                                    jnp.float32(n_rows), weight)
            grad = g if grad is None else add(grad, g)
            main, extra, rows = main + a, extra + b, rows + c
        del g
        if m is None:
            m = jax.tree.map(jnp.zeros_like, p)
            v = jax.tree.map(jnp.zeros_like, p)
        else:
            m, v = jax.device_put((m, v))
        p, m, v = update(p, m, v, grad, jnp.float32(lr), jnp.float32(t))
        del grad
        bias = bias_step(bias, rows, speed)
        out["loss_main"].append(float(main))
        out["loss_mtp"].append(float(extra))
        out["expert_rows"].append(np.asarray(rows))
        if t < len(lrs):
            m, v = jax.device_get((m, v))     # room for the next gradients
    out.update(
        moment_norm={n: float(x) for n, x in norms(m).items()},
        second_moment_root={n: float(x) for n, x in root_sums(v).items()},
        delta_norm={n: float(x) for n, x in delta_norms(p, key).items()},
        bias=np.asarray(bias))
    return out

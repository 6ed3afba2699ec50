"""Plain reference of the Qwen3-Next decoder (``model_type`` ``qwen3_next``):
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching, no chunks, and nothing of the program is
imported.

The equations, pre-norm residual, no biases; ``N(x) = x / rms(x) * (1 + w)``
(a zero-centred weight, ``rms_norm_eps``), ``h = N(x)``; layer ``l`` is full
attention where ``(l + 1) % full_attention_interval == 0``, else the delta rule;
every layer's second half is the expert layer:

* gated delta rule: ``[q | k | v | z] = h W_qkvz`` (key_dim, key_dim, value_dim,
  value_dim), ``[b | a] = h W_ba`` (value heads each); ``[q | k | v]`` pass a
  causal depthwise convolution of ``linear_conv_kernel_dim`` taps (zeros before
  the start, no bias), then SiLU; ``q``, ``k``: ``linear_num_key_heads`` heads,
  each ``x / sqrt(sum x^2 + 1e-6)``, ``q`` times ``head_k_dim^-1/2``, each
  repeated to the consecutive value heads it serves.  A value head keeps a
  matrix ``S`` (head_k_dim, head_v_dim), zero before the first token, and a
  token does, TOKEN BY TOKEN (a ``lax.scan`` over positions): ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; ``S' = exp(g) S``;
  ``d = beta (v - S'^T k)``; ``S = S' + k d^T``; ``o = S^T q``.  Output ``y =
  (w_n o / rms(o)) SiLU(z)`` a head (a plain weight), concatenated, ``W_o``;
* gated attention: ``[q | gate] = h W_q`` a head (head_dim each), ``k``, ``v``;
  ``q``, ``k`` pass ``N`` over the head; rotary (pairs (2i, 2i+1)) on the first
  ``partial_rotary_factor`` of the head; causal softmax at ``head_dim^-1/2``,
  every key; ``y = (attn sigmoid(gate)) W_o``;
* experts: ``p = softmax(h' W_r)`` over all ``router_experts``, the top
  ``num_experts_per_tok``, ``g = p[choice] / sum p[choice]``; the output is the
  weighted sum of the chosen experts' SwiGLUs that lie in ``experts_held``
  (first, count) (what the other chips' experts would add is left out) plus
  ``sigmoid(h' w_sg)`` times the shared expert's SwiGLU;
* model: embedding, the layers, a final ``N``, an untied head over the
  ``vocab_size`` rows held.  The multi-token-prediction layer is not in it.

Departures (the configuration's ``assumed``): rotary pairs (2i, 2i+1); the
rows of ``W_qkvz`` and ``W_ba`` in the plain order above (the port interleaves
them by key head: a fixed permutation seeded weights cannot tell apart); the
norms' weights zero and the gated norm's one; SEEDED GATES THAT LEAVE THE STATE
A MEMORY: ``dt_bias`` ones and ``A_log`` such that a head's decay a token at
``a`` = 0 is ``exp(-r)`` with ``r`` log-uniform between ``-ln 0.999`` and
``-ln 0.9`` (the published initialiser's ``A`` uniform on (0, 16) forgets within
a token, and a lost state would then move no logit).

The weights are made here from the seed, a layer at a time and the experts a
group at a time (the check runs beside the served weights and 3 GiB of state);
an expert's matrices come from the key of its index in the layer, so any share
holds the same values; the benchmark hands the same values to the program,
never the other way round.  Two controls take the program's place in the
comparison: ``lowp`` rounds every matrix product's operands, and the rule's
``q``, ``k`` and ``v``, to float8 (the step below the bfloat16 the
configuration states); ``reset_at`` zeroes a sequence's recurrent states before
the token at that position: a server whose prefill did not hand its state over.

The shared arithmetic (float8 rounding, RMSNorm, rotation, blocked causal
attention) and ``served_gaps`` are ``references/llama.py``'s own code: that file
is loaded here under a name of its own.
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
    "chipbench_reference_qwen3_next_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

_fp8, _mm, _rms, _rope, _attention = (_base._fp8, _base._mm, _base._rms,
                                      _base._rope, _base._attention)
layer_key, top_key = _base.layer_key, _base.top_key

L2_EPS = 1e-6
DECAY_LO, DECAY_HI = 0.9, 0.999   # a head's decay a token at a = 0
EXPERT_GROUP = 8                  # experts made and computed at a time
ROW_BLOCK = 2048                  # rows whose logits are on the device at a time


def _normal(key, shape, dtype, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dtype)


def _f32(a):
    return a.astype(jnp.float32)


def layer_kind(cfg, l):
    return "attention" if (l + 1) % cfg["full_attention_interval"] == 0 \
        else "delta"


def _dims(cfg):
    """(key_dim, value_dim, channels through the convolution)."""
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return kd, vd, 2 * kd + vd


def layer_shapes(cfg, kind):
    """Leaf name -> shape of the seeded Normal leaves, without the expert bank;
    matrices are (out, in), the convolution's taps (tap, channel)."""
    h, s = cfg["hidden_size"], cfg["shared_expert_intermediate_size"]
    out = {"router": (cfg["router_experts"], h), "shared_gate": (s, h),
           "shared_up": (s, h), "shared_down": (h, s),
           "shared_expert_gate": (1, h)}
    if kind == "delta":
        _kd, vd, conv = _dims(cfg)
        out.update(in_qkvz=(conv + vd, h),
                   in_ba=(2 * cfg["linear_num_value_heads"], h),
                   conv=(cfg["linear_conv_kernel_dim"], conv),
                   out_proj=(h, vd))
    else:
        hd = cfg["head_dim"]
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out.update(q=(nq * 2 * hd, h), k=(nkv * hd, h), v=(nkv * hd, h),
                   o=(h, nq * hd))
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
    zeros = jnp.zeros((cfg["hidden_size"],), dtype)
    w.update(op_norm=zeros, ffn_norm=zeros)
    if kind == "delta":
        nv = cfg["linear_num_value_heads"]
        rate = jnp.exp(jax.random.uniform(
            keys[-1], (nv,), jnp.float32, np.log(-np.log(DECAY_HI)),
            np.log(-np.log(DECAY_LO))))
        # g = -exp(A_log) softplus(0 + 1) = -rate
        w.update(A_log=jnp.log(rate / jax.nn.softplus(1.0)).astype(dtype),
                 dt_bias=jnp.ones((nv,), dtype),
                 out_norm=jnp.ones((cfg["linear_value_head_dim"],), dtype))
    else:
        w.update(q_norm=jnp.zeros((cfg["head_dim"],), dtype),
                 k_norm=jnp.zeros((cfg["head_dim"],), dtype))
    if experts:
        w.update(init_experts(key, cfg, dtype, *cfg["experts_held"]))
    return w


def init_top(key, cfg, dtype):
    ke, kh = jax.random.split(key)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"emb": _normal(ke, (v, h), dtype, cfg),
            "head": _normal(kh, (v, h), dtype, cfg),
            "norm": jnp.zeros((h,), dtype)}


# -- the layer ----------------------------------------------------------------------

def _norm(x, w, eps):
    return _rms(x, 1.0 + w, eps)


def delta_rule(u, w, cfg, lowp=False, reset_at=-1):
    """u (T, hidden) -> (T, hidden); the recurrence token by token.
    ``reset_at``: the position before whose token the states are zeroed (-1:
    never)."""
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, _vd, channels = _dims(cfg)
    taps, t = cfg["linear_conv_kernel_dim"], u.shape[0]
    qkvz = _mm(u, w["in_qkvz"], lowp)
    mixed, z = qkvz[:, :channels], qkvz[:, channels:]
    ba = _mm(u, w["in_ba"], lowp)
    xp = jnp.concatenate([jnp.zeros((taps - 1, channels), mixed.dtype), mixed])
    conv = jax.nn.silu(sum(w["conv"][j] * xp[j:j + t] for j in range(taps)))

    def unit(a):
        a = a.reshape(t, nk, dk)
        a = a / jnp.sqrt((a * a).sum(-1, keepdims=True) + L2_EPS)
        return jnp.repeat(a, nv // nk, axis=1)

    q, k = unit(conv[:, :kd]) * dk ** -0.5, unit(conv[:, kd:2 * kd])
    v = conv[:, 2 * kd:].reshape(t, nv, dv)
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, nv:] + w["dt_bias"])

    def one(s, row):
        q, k, v, beta, g, keep = row
        s = s * keep * jnp.exp(g)[:, None, None]
        d = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = s + k[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q)

    keep = (jnp.arange(t) != reset_at).astype(jnp.float32)
    _s, o = jax.lax.scan(one, jnp.zeros((nv, dk, dv), jnp.float32),
                         (q, k, v, beta, g, keep))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"]) * w["out_norm"]
    o = o * jax.nn.silu(z.reshape(t, nv, dv))
    return _mm(o.reshape(t, -1), w["out_proj"], lowp)


def attention(u, w, cfg, lowp=False):
    hd, theta, eps = cfg["head_dim"], cfg["rope_theta"], cfg["rms_norm_eps"]
    rd = int(hd * cfg["partial_rotary_factor"])
    t = u.shape[0]
    qg = _mm(u, w["q"], lowp).reshape(t, -1, 2 * hd)
    q, gate = _norm(qg[..., :hd], w["q_norm"], eps), qg[..., hd:]
    k = _norm(_mm(u, w["k"], lowp).reshape(t, -1, hd), w["k_norm"], eps)
    v = _mm(u, w["v"], lowp).reshape(t, -1, hd)
    q, k = (jnp.concatenate([_rope(a[..., :rd], theta), a[..., rd:]], axis=-1)
            for a in (q, k))
    ctx = _attention(q, k, v, lowp) * jax.nn.sigmoid(gate)
    return _mm(ctx.reshape(t, -1), w["o"], lowp)


def combine_weights(u, w, cfg, lowp=False):
    """-> ((T, count) float32: a HELD expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in router logits)."""
    k = cfg["num_experts_per_tok"]
    first, count = cfg["experts_held"]
    logits = _mm(u, w["router"], lowp)
    p = jax.nn.softmax(logits, axis=-1)
    lead, idx = jax.lax.top_k(logits, k + 1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    comb = jnp.zeros_like(p).at[rows, idx].add(chosen)
    return comb[:, first:first + count], margin


def _swiglu(u, gate, up, down, lowp):
    g = _mm(u, gate, lowp)
    return _mm(jax.nn.silu(g) * _mm(u, up, lowp), down, lowp)


def experts_part(u, comb, bank, lowp=False):
    """What the experts of ``bank`` add for rows u (N, hidden): every one of
    them on every row, weighted by its columns of ``comb`` (N, count)."""
    def one(acc, e):
        wg, wu, wd, c = e
        y = _swiglu(u, _f32(wg).T, _f32(wu).T, _f32(wd).T, lowp)
        return acc + c[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (bank["w_gate"], bank["w_up"], bank["w_down"], comb.T))
    return acc


def layer_front(x, w, cfg, kind, lowp=False, reset_at=-1):
    """The operator with its residual and the gated shared expert, and what the
    held experts need: -> (x, the normed rows, the held experts' combine
    weights, the choice margins).  x (T, hidden) float32."""
    w = {n: _f32(a) for n, a in w.items()}
    eps = cfg["rms_norm_eps"]
    h = _norm(x, w["op_norm"], eps)
    x = x + (delta_rule(h, w, cfg, lowp, reset_at) if kind == "delta"
             else attention(h, w, cfg, lowp))
    h = _norm(x, w["ffn_norm"], eps)
    shared = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                     lowp) * jax.nn.sigmoid(_mm(h, w["shared_expert_gate"], lowp))
    return (x + shared, h) + combine_weights(h, w, cfg, lowp)


def layer_forward(x, w, cfg, kind, lowp=False, reset_at=-1):
    """One whole layer over one sequence from a full set of weights (the tests'
    sizes; ``forward_rows`` makes the experts in groups instead)."""
    x, h, comb, _margin = layer_front(x, w, cfg, kind, lowp, reset_at)
    return x + experts_part(h, comb, {n: w[n] for n in
                                      ("w_gate", "w_up", "w_down")}, lowp)


def forward(cfg, weights, ids, lowp=False, reset_at=-1):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}``."""
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["top"]["emb"])[jnp.asarray(ids)]
        for l, w in enumerate(weights["layers"]):
            x = layer_forward(x, w, cfg, layer_kind(cfg, l), lowp, reset_at)
        h = _norm(x, _f32(weights["top"]["norm"]), cfg["rms_norm_eps"])
        return _mm(h, _f32(weights["top"]["head"]), lowp)


# -- the forward pass of the check, a layer's weights at a time ------------------

@functools.lru_cache(maxsize=None)
def _programs(cfg_json, dtype_name, lowp):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def embed(seed_key, ids):
        top = init_top(top_key(seed_key), cfg, dtype)
        return _f32(top["emb"])[ids]

    @functools.partial(jax.jit, static_argnums=4)
    def front(seed_key, l, xs, reset_at, kind):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, kind, experts=False)
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda a: layer_front(a[0], w, cfg, kind, lowp, a[1]),
                (xs, reset_at))

    @functools.partial(jax.jit, donate_argnums=5)
    def group(seed_key, l, first, hs, combs, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype,
                            cfg["experts_held"][0] + first, EXPERT_GROUP)
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
            h = _norm(picked, _f32(top["norm"]), cfg["rms_norm_eps"])
            return _mm(h, _f32(top["head"]), lowp), \
                margins[rows[:, 0], rows[:, 1]]

    return embed, front, group, logits


def _cfg_json(cfg):
    keep = ("hidden_size", "num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "rms_norm_eps",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "router_experts", "experts_held",
            "num_experts_per_tok", "moe_intermediate_size",
            "shared_expert_intermediate_size", "norm_topk_prob", "vocab_size",
            "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, lowp=False, with_margin=False,
                 reset_at=None):
    """Logits of the full forward pass at chosen positions, weights remade from
    the seed: ``ids`` (N, T) int32, every sequence padded at its end; ``rows``
    (M, 2) pairs (sequence, position).  A layer's weights, and of its experts a
    group's, on the device at a time.  ``reset_at`` (N,): see ``delta_rule``.
    -> (M, vocab) float32 on the host; ``with_margin`` adds (M,): the smallest
    choice margin of the row's token over the layers."""
    embed, front, group, logits = _programs(_cfg_json(cfg), cfg["torch_dtype"],
                                            bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    held = cfg["experts_held"][1]
    assert held % EXPERT_GROUP == 0
    ids = jnp.asarray(ids, jnp.int32)
    reset = jnp.full((ids.shape[0],), -1, jnp.int32) if reset_at is None \
        else jnp.asarray(reset_at, jnp.int32)
    xs = embed(key, ids)
    margins = jnp.full(xs.shape[:2], jnp.inf, jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        xs, hs, combs, margin = front(key, jnp.int32(l), xs, reset,
                                      layer_kind(cfg, l))
        margins = jnp.minimum(margins, margin)
        acc = jnp.zeros_like(xs)
        for first in range(0, held, EXPERT_GROUP):
            acc = group(key, jnp.int32(l), jnp.int32(first), hs, combs, acc)
        xs = xs + acc
        del hs, combs, acc
    # the logits a block of rows at a time: 8,192 rows of 37,984 are 1.2 GB
    rows = np.asarray(rows, np.int32)
    padded = np.concatenate([rows, np.repeat(rows[:1], -len(rows) % ROW_BLOCK,
                                             axis=0)])
    out, margin = zip(*(
        tuple(np.asarray(a) for a in logits(key, xs, jnp.asarray(block), margins))
        for block in padded.reshape(-1, ROW_BLOCK, 2)))
    out = np.concatenate(out)[:len(rows)]
    return (out, np.concatenate(margin)[:len(rows)]) if with_margin else out


def served_gaps(cfg, seed, prompts, served, pad_to, max_rows, control=None):
    """``served_gaps`` as the Llama reference decides it, over this file's
    forward pass: the private copy of that module calls ``forward_rows`` by its
    global name, which is bound here.  -> (gaps, the float32 pass's choice
    margin at each of those tokens: a token whose margin is small may go to
    another expert under bfloat16 activations, and its gap then says nothing of
    the program's arithmetic).  ``control`` puts a reference in the program's
    place: ``"lowp"`` the float8 one, ``"lost_state"`` the float32 one whose
    recurrent states are zeroed after each prompt (the prefill's state not
    handed over)."""
    kept = {}

    def rows_fn(cfg, seed, ids, rows, lowp=False):
        kw = {} if not lowp else {"lowp": True} if control == "lowp" \
            else {"reset_at": [len(p) for p in prompts]}
        out, margin = forward_rows(cfg, seed, ids, rows, with_margin=True, **kw)
        if not lowp:
            kept["margin"] = margin
        return out

    assert control in (None, "lowp", "lost_state")
    _base.forward_rows = rows_fn
    gaps = _base.served_gaps(cfg, seed, prompts, served, pad_to, max_rows,
                             lowp_control=control is not None)
    return gaps, kept["margin"][:len(gaps)]

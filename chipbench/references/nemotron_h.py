"""Plain reference of the Nemotron-H decoder (``model_type`` ``nemotron_h``;
Nemotron 3 Super): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no batching,
no chunks, and nothing of the program is imported.

The equations, pre-norm residual, no bias but the convolution's: ``x <- x +
f(N(x))``, ``N(x) = w x / rms(x)`` (a plain weight, ``norm_eps``), ``u = N(x)``;
layer ``l`` is what letter ``l`` of ``hybrid_override_pattern`` says:

* ``M``, the Mamba-2 mixer (``mamba_num_heads`` heads of ``mamba_head_dim``,
  ``d_inner`` their product; ``n_groups`` groups, ``ssm_state_size`` ``N_s``):
  ``[z | xBC | dt] = u W_in`` (d_inner, d_inner + 2 G N_s, heads); ``xBC``
  passes a causal depthwise convolution of ``conv_kernel`` taps (zeros before
  the start) WITH a bias, then SiLU; ``[x | B | C] = xBC``.  A head ``h`` of
  group ``g = h // (heads / G)`` keeps a matrix ``S`` (head_dim, N_s), zero
  before the first token, and a token does, TOKEN BY TOKEN (a ``lax.scan`` over
  positions): ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``,
  ``S = a S + dt x B_g^T``, ``y = S C_g + D x``.  Then the gated group norm, the
  gate first: ``y <- w_n (y SiLU(z)) / rms_group(y SiLU(z))`` over each of the G
  groups of d_inner / G channels; ``f = y W_out``;
* ``*``, attention: ``q``, ``k``, ``v`` (``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``), causal softmax at
  ``head_dim^-1/2``, every key; ``f = o W_o``.  No rotary embedding, no head
  norm, no gate;
* ``E``, latent experts: ``s = sigmoid(u W_r)`` over all ``router_experts``
  (the FULL-width rows), the top ``num_experts_per_tok`` of ``s + b`` (the choice
  bias), ``g = s[choice] / (sum s[choice]) * routed_scaling_factor``; ``l = u
  W_dn`` (``moe_latent_size``); the output is ``(sum_e g_e relu(l W1_e)^2 W2_e)
  W_up`` over the chosen experts that lie in ``experts_held`` (first, count)
  (what the other chips' experts would add is left out; the sum is taken in the
  latent space, ``W_up`` once) plus the shared expert ``relu(u V1)^2 V2`` at the
  model's width;
* model: embedding, the layers, a final ``N``, an untied head over the
  ``vocab_size`` rows held.  The multi-token-prediction module is not in it.

Departures (the configuration's ``assumed``): no rotary embedding in the
attention layers; ``A_log``, ``dt_bias`` and ``D`` from Mamba-2's published
initialiser (``dt`` log-uniform between ``time_step_min`` and
``time_step_max``, floored at ``time_step_floor``, ``dt_bias`` its inverse
softplus, ``A`` uniform on (1, 16), ``D`` ones); THE CONVOLUTION'S TAPS AND BIAS
AS THE PUBLISHED IMPLEMENTATION'S DEPTHWISE ``Conv1d`` IS BORN, uniform on
``+-1/sqrt(conv_kernel)`` = +-0.5, not Normal(0, 0.02): under taps of 0.02
``x``, ``B`` and ``C`` are some 0.03 and the state's read-out ``S C`` is a
thousandth of the skip ``D x``, so a lost state would move no logit (measured:
a mixer's output moves by 8e-4 of its norm where the state is zeroed, by 0.25
to 0.36 under these taps; ``control_state.*`` read 2.6e-6 on the chip under
the Normal taps); the choice bias zero; the orders ``[z | xBC | dt]`` and
``[x | B | C]`` and the convolution's (tap, channel) layout; the norms' weights
one.

The weights are made here from the seed, a layer at a time and the experts a
group at a time (the check runs beside the served weights and 2.7 GB of state:
one expert layer's held bank alone is 2.8 GB in float32); an expert's matrices
come from the key of its index in the layer, so any share holds the same
values; the benchmark hands the same values to the program, never the other way
round.  Two controls take the program's place in the comparison: ``lowp``
rounds every matrix product's operands, and the scan's ``x``, ``B`` and ``C``,
to float8 (the step below the bfloat16 the configuration states); ``reset_at``
zeroes a sequence's recurrent states before the token at that position: a
server whose prefill did not hand its state over.

The shared arithmetic (float8 rounding, RMSNorm, blocked causal attention) and
``served_gaps`` are ``references/llama.py``'s own code: that file is loaded here
under a name of its own.
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
    "chipbench_reference_nemotron_h_shared",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "llama.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

_fp8, _mm, _rms, _attention = (_base._fp8, _base._mm, _base._rms,
                               _base._attention)
layer_key, top_key = _base.layer_key, _base.top_key

A_LO, A_HI = 1.0, 16.0            # Mamba-2's initialiser: A uniform on (1, 16)
EXPERT_GROUP = 8                  # experts made and computed at a time
ROW_BLOCK = 2048                  # rows whose logits are on the device at a time
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def _normal(key, shape, dtype, cfg):
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dtype)


def _f32(a):
    return a.astype(jnp.float32)


def layer_kind(cfg, l):
    return KINDS[cfg["hybrid_override_pattern"][l]]


def _dims(cfg):
    """(d_inner, B's or C's width, channels through the convolution)."""
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return di, gn, di + 2 * gn


def layer_shapes(cfg, kind):
    """Leaf name -> shape of the seeded leaves (Normal, but the convolution's,
    uniform), without the expert bank; matrices are (out, in), the
    convolution's taps (tap, channel)."""
    h = cfg["hidden_size"]
    if kind == "mamba":
        di, _gn, conv = _dims(cfg)
        return {"in_proj": (di + conv + cfg["mamba_num_heads"], h),
                "conv": (cfg["conv_kernel"], conv), "conv_bias": (conv,),
                "out_proj": (h, di)}
    if kind == "attention":
        hd = cfg["head_dim"]
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        return {"q": (nq * hd, h), "k": (nkv * hd, h), "v": (nkv * hd, h),
                "o": (h, nq * hd)}
    lat, s = cfg["moe_latent_size"], cfg["moe_shared_expert_intermediate_size"]
    return {"router": (cfg["router_experts"], h), "latent_down": (lat, h),
            "latent_up": (h, lat), "shared_up": (s, h), "shared_down": (h, s)}


def init_experts(key, cfg, dtype, first, count):
    """Experts ``first .. first + count`` OF THE LAYER (not of the held part),
    stacked: ``w_up`` (count, latent, width), ``w_down`` (count, width, latent),
    each (in, out); every expert's values come from its own key, so any
    division of the layer over chips makes the same values."""
    lat, i = cfg["moe_latent_size"], cfg["moe_intermediate_size"]

    def one(e):
        ku, kd = jax.random.split(jax.random.fold_in(key, 1000 + e))
        return {"w_up": _normal(ku, (lat, i), dtype, cfg),
                "w_down": _normal(kd, (i, lat), dtype, cfg)}

    return jax.vmap(one)(first + jnp.arange(count))


def init_layer(key, cfg, dtype, kind, experts=True):
    """One layer's weights from its key; ``experts=False`` leaves the expert
    bank out (the forward pass makes it a group at a time).  An expert layer's
    norm is ``ffn_norm``, a mixer's ``norm``: the program keeps a mixer and the
    expert layer behind it as one served layer."""
    shapes = layer_shapes(cfg, kind)
    keys = jax.random.split(key, len(shapes) + 2)
    bound = cfg["conv_kernel"] ** -0.5    # a depthwise Conv1d's default init
    w = {n: jax.random.uniform(k, shapes[n], jnp.float32, -bound, bound)
         .astype(dtype) if n in ("conv", "conv_bias")
         else _normal(k, shapes[n], dtype, cfg)
         for k, n in zip(keys, sorted(shapes))}
    ones = jnp.ones((cfg["hidden_size"],), dtype)
    if kind == "experts":
        w.update(ffn_norm=ones,
                 expert_bias=jnp.zeros((cfg["router_experts"],), dtype))
        if experts:
            w.update(init_experts(key, cfg, dtype, *cfg["experts_held"]))
        return w
    w.update(norm=ones)
    if kind == "mamba":
        nh = cfg["mamba_num_heads"]
        dt = jnp.exp(jax.random.uniform(
            keys[-2], (nh,), jnp.float32, np.log(cfg["time_step_min"]),
            np.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        a = jax.random.uniform(keys[-1], (nh,), jnp.float32, A_LO, A_HI)
        # softplus(dt_bias) = dt
        w.update(dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                 A_log=jnp.log(a).astype(dtype), D=jnp.ones((nh,), dtype),
                 out_norm=jnp.ones((_dims(cfg)[0],), dtype))
    return w


def init_top(key, cfg, dtype):
    ke, kh = jax.random.split(key)
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return {"emb": _normal(ke, (v, h), dtype, cfg),
            "head": _normal(kh, (v, h), dtype, cfg),
            "norm": jnp.ones((h,), dtype)}


# -- the layers -----------------------------------------------------------------------

def mamba(u, w, cfg, lowp=False, reset_at=-1):
    """u (T, hidden) -> (T, hidden); the recurrence token by token.
    ``reset_at``: the position before whose token the states are zeroed (-1:
    never)."""
    nh, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, ns = cfg["n_groups"], cfg["ssm_state_size"]
    di, gn, channels = _dims(cfg)
    taps, t = cfg["conv_kernel"], u.shape[0]
    zxd = _mm(u, w["in_proj"], lowp)
    z, mixed, dt = zxd[:, :di], zxd[:, di:di + channels], zxd[:, di + channels:]
    xp = jnp.concatenate([jnp.zeros((taps - 1, channels), mixed.dtype), mixed])
    conv = jax.nn.silu(sum(w["conv"][j] * xp[j:j + t] for j in range(taps))
                       + w["conv_bias"])
    x = conv[:, :di].reshape(t, nh, p)
    B = conv[:, di:di + gn].reshape(t, g, ns)
    C = conv[:, di + gn:].reshape(t, g, ns)
    if lowp:
        x, B, C = _fp8(x), _fp8(B), _fp8(C)
    B, C = (jnp.repeat(a, nh // g, axis=1) for a in (B, C))   # a head's own
    dt = jax.nn.softplus(dt + w["dt_bias"])
    decay = jnp.exp(-jnp.exp(w["A_log"]) * dt)

    def one(s, row):
        x, B, C, dt, decay, keep = row
        s = s * keep * decay[:, None, None] \
            + (dt[:, None] * x)[:, :, None] * B[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, C) + w["D"][:, None] * x

    keep = (jnp.arange(t) != reset_at).astype(jnp.float32)
    _s, y = jax.lax.scan(one, jnp.zeros((nh, p, ns), jnp.float32),
                         (x, B, C, dt, decay, keep))
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, g, di // g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg["norm_eps"])
    return _mm(y.reshape(t, di) * w["out_norm"], w["out_proj"], lowp)


def attention(u, w, cfg, lowp=False):
    hd, t = cfg["head_dim"], u.shape[0]
    q = _mm(u, w["q"], lowp).reshape(t, -1, hd)
    k = _mm(u, w["k"], lowp).reshape(t, -1, hd)
    v = _mm(u, w["v"], lowp).reshape(t, -1, hd)
    return _mm(_attention(q, k, v, lowp).reshape(t, -1), w["o"], lowp)


def combine_weights(u, w, cfg, lowp=False):
    """-> ((T, count) float32: a HELD expert's weight for a row, zero where it
    was not among the row's chosen; (T,) the row's choice margin: by how much
    the last expert chosen leads the first one left out, in router logits)."""
    k = cfg["num_experts_per_tok"]
    first, count = cfg["experts_held"]
    logits = _mm(u, w["router"], lowp)
    s = jax.nn.sigmoid(logits)
    _lead, idx = jax.lax.top_k(s + w["expert_bias"], k + 1)
    lead = jnp.take_along_axis(logits, idx, axis=-1)
    margin, idx = lead[:, k - 1] - lead[:, k], idx[:, :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    chosen = chosen * cfg["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    comb = jnp.zeros_like(s).at[rows, idx].add(chosen)
    return comb[:, first:first + count], margin


def _relu2(u, up, down, lowp):
    return _mm(jnp.square(jax.nn.relu(_mm(u, up, lowp))), down, lowp)


def experts_part(lat, comb, bank, lowp=False):
    """What the experts of ``bank`` add IN THE LATENT SPACE for rows ``lat`` (N,
    latent): every one of them on every row, weighted by its columns of
    ``comb`` (N, count)."""
    def one(acc, e):
        wu, wd, c = e
        return acc + c[:, None] * _relu2(lat, _f32(wu).T, _f32(wd).T, lowp), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                          (bank["w_up"], bank["w_down"], comb.T))
    return acc


def layer_front(x, w, cfg, kind, lowp=False, reset_at=-1):
    """A mixer with its residual: -> x.  An expert layer up to its held
    experts: -> (x + the shared expert, the latent rows, the held experts'
    combine weights, the choice margins).  x (T, hidden) float32."""
    w = {n: _f32(a) for n, a in w.items()}
    eps = cfg["norm_eps"]
    if kind != "experts":
        u = _rms(x, w["norm"], eps)
        return x + (mamba(u, w, cfg, lowp, reset_at) if kind == "mamba"
                    else attention(u, w, cfg, lowp))
    u = _rms(x, w["ffn_norm"], eps)
    shared = _relu2(u, w["shared_up"], w["shared_down"], lowp)
    return (x + shared, _mm(u, w["latent_down"], lowp)) \
        + combine_weights(u, w, cfg, lowp)


def layer_back(x, acc, w, lowp=False):
    """An expert layer's end: the held experts' latent sum through ``W_up``."""
    return x + _mm(acc, _f32(w["latent_up"]), lowp)


def layer_forward(x, w, cfg, kind, lowp=False, reset_at=-1):
    """One whole layer over one sequence from a full set of weights (the tests'
    sizes; ``forward_rows`` makes the experts in groups instead)."""
    if kind != "experts":
        return layer_front(x, w, cfg, kind, lowp, reset_at)
    x, lat, comb, _margin = layer_front(x, w, cfg, kind, lowp)
    return layer_back(x, experts_part(lat, comb, w, lowp), w, lowp)


def forward(cfg, weights, ids, lowp=False, reset_at=-1):
    """Logits (T, vocab) of one sequence from given weights ``{"top": ...,
    "layers": [...]}``, a layer a letter of the pattern."""
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["top"]["emb"])[jnp.asarray(ids)]
        for l, w in enumerate(weights["layers"]):
            x = layer_forward(x, w, cfg, layer_kind(cfg, l), lowp, reset_at)
        h = _rms(x, _f32(weights["top"]["norm"]), cfg["norm_eps"])
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
    def group(seed_key, l, first, lats, combs, acc):
        bank = init_experts(layer_key(seed_key, l), cfg, dtype,
                            cfg["experts_held"][0] + first, EXPERT_GROUP)
        n, t, h = lats.shape
        comb = jax.lax.dynamic_slice_in_dim(combs.reshape(n * t, -1), first,
                                            EXPERT_GROUP, axis=1)
        with jax.default_matmul_precision("highest"):
            return acc + experts_part(lats.reshape(n * t, h), comb, bank,
                                      lowp).reshape(n, t, h)

    @jax.jit
    def back(seed_key, l, xs, acc):
        w = init_layer(layer_key(seed_key, l), cfg, dtype, "experts",
                       experts=False)
        with jax.default_matmul_precision("highest"):
            return layer_back(xs, acc, w, lowp)

    @jax.jit
    def logits(seed_key, xs, rows, margins):
        top = init_top(top_key(seed_key), cfg, dtype)
        picked = xs[rows[:, 0], rows[:, 1]]
        with jax.default_matmul_precision("highest"):
            h = _rms(picked, _f32(top["norm"]), cfg["norm_eps"])
            return _mm(h, _f32(top["head"]), lowp), \
                margins[rows[:, 0], rows[:, 1]]

    return embed, front, group, back, logits


def _cfg_json(cfg):
    keep = ("hidden_size", "hybrid_override_pattern", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "num_attention_heads", "num_key_value_heads", "head_dim", "norm_eps",
            "router_experts", "experts_held", "num_experts_per_tok",
            "moe_latent_size", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "norm_topk_prob",
            "routed_scaling_factor", "time_step_min", "time_step_max",
            "time_step_floor", "vocab_size", "initializer_range")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)


def forward_rows(cfg, seed, ids, rows, lowp=False, with_margin=False,
                 reset_at=None):
    """Logits of the full forward pass at chosen positions, weights remade from
    the seed: ``ids`` (N, T) int32, every sequence padded at its end; ``rows``
    (M, 2) pairs (sequence, position).  A layer's weights, and of its experts a
    group's, on the device at a time.  ``reset_at`` (N,): see ``mamba``.
    -> (M, vocab) float32 on the host; ``with_margin`` adds (M,): the smallest
    choice margin of the row's token over the expert layers."""
    embed, front, group, back, logits = _programs(
        _cfg_json(cfg), cfg["torch_dtype"], bool(lowp))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    held = cfg["experts_held"][1]
    assert held % EXPERT_GROUP == 0
    ids = jnp.asarray(ids, jnp.int32)
    reset = jnp.full((ids.shape[0],), -1, jnp.int32) if reset_at is None \
        else jnp.asarray(reset_at, jnp.int32)
    xs = embed(key, ids)
    margins = jnp.full(xs.shape[:2], jnp.inf, jnp.float32)
    for l in range(len(cfg["hybrid_override_pattern"])):
        kind = layer_kind(cfg, l)
        if kind != "experts":
            xs = front(key, jnp.int32(l), xs, reset, kind)
            continue
        xs, lats, combs, margin = front(key, jnp.int32(l), xs, reset, kind)
        margins = jnp.minimum(margins, margin)
        acc = jnp.zeros_like(lats)
        for first in range(0, held, EXPERT_GROUP):
            acc = group(key, jnp.int32(l), jnp.int32(first), lats, combs, acc)
        xs = back(key, jnp.int32(l), xs, acc)
        del lats, combs, acc
    # the logits a block of rows at a time: 8,192 rows of 32,768 are 1.1 GB
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

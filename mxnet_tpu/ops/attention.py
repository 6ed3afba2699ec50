"""Attention operators.

Reference: ``src/operator/contrib/transformer.cc:?`` — the
``interleaved_matmul_selfatt_qk/valatt`` + ``div_sqrt_dim`` ops GluonNLP's
BERT uses for fused self-attention.

TPU-native: one fused ``dot_product_attention`` op (the Pallas flash
kernel on TPU where its shape rules hold, jax.nn's XLA softmax(QKᵀ)V
fusion otherwise), plus reference-compatible wrappers for the interleaved
contrib ops.  bf16 inputs accumulate in fp32 on the MXU.
"""
from __future__ import annotations

import sys

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import apply_op, make_exporter

_this = sys.modules[__name__]
_export = make_exporter(_this)


def sdpa_raw(q, k, v, m=None, scale=None, causal=False):
    """Raw-array fused attention: the Pallas flash kernels when they apply
    (TPU, unmasked/causal, 128-aligned lengths), else jax.nn's
    ``dot_product_attention``.  Shared by the NDArray op below and the
    sequence-parallel bodies (parallel/ring.py).

    Layout here is (B, T, N, H), a free reshape of the projections'
    (B, T, N x H).  Where the operands' shapes allow
    (``flash_attention.tokens_applicable``: heads that fill 128-lane
    tiles, one tile of sequence, one chip) the token-major kernels read
    and write that layout where it lies.  Otherwise the head-major
    kernels take (B, N, T, H): q, k, v are transposed in and o out, and
    the backward transposes do in and dq, dk, dv out."""
    if m is None and q.shape[1] == k.shape[1] and \
            q.shape[2] == k.shape[2] and \
            q.shape[1] % 128 == 0 and q.shape[-1] <= 256:
        # equal-head, unmasked, 128-aligned: the Pallas kernel applies
        # (GQA/MQA head broadcasting stays on the jax.nn path)
        from .flash_attention import (_on_tpu, flash_attention_raw,
                                      flash_attention_tokens,
                                      tokens_applicable)

        if _on_tpu():
            # mxlint: allow=T2 (the rule reads static shapes)
            if tokens_applicable(q, k, v):
                b, t, n, h = q.shape
                return flash_attention_tokens(
                    *(a.reshape(b, t, n * h) for a in (q, k, v)), n, causal,
                    scale).reshape(b, t, n, h)
            qt = q.transpose(0, 2, 1, 3)
            out = flash_attention_raw(qt, k.transpose(0, 2, 1, 3),
                                      v.transpose(0, 2, 1, 3), causal,
                                      scale)
            return out.transpose(0, 2, 1, 3)
    if m is not None and m.dtype != jnp.bool_:
        m = m.astype(jnp.bool_)
    return jax.nn.dot_product_attention(
        q, k, v, mask=m, scale=scale, is_causal=causal)


def masked_attention(q, k, v, mask):
    """The served decoders' dense attention: scores in f32 accumulation
    (matches ``_sdpa_ref``), masked softmax, context.  q (B,H,Q,D); k/v
    (B,Hkv,T,D), repeated here for GQA; mask (Q,T) shared across the
    batch, or already broadcastable to (B,H,Q,T) — the per-slot serving
    step masks each batch row at its own cache length.

    Where it still runs (``models/decoder.py``'s views): the suffix
    behind a radix prefix (``BehindPrefix``), the dense caches of
    offline ``generate`` (``DenseCache``), the
    paged pool's gather path (``ops.paged_attention.window_attention``),
    and whole-sequence prefill (``Causal``) on the CPU, on a mesh-placed
    engine and at the buckets
    ``ops.flash_attention.prefill_applicable`` refuses.  It is the
    reference the Pallas kernels are tested against: the score tensor
    ``(B, H, Q, T)`` in float32 and the repeated K/V are real arrays
    here."""
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhtd->bhqt", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    if mask.ndim == 2:
        mask = mask[None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bhtd->bhqd", attn, v)


def dot_product_attention(query, key, value, mask=None, scale=None,
                          dropout=0.0, causal=False, **kwargs):
    """Fused scaled-dot-product attention.

    query/key/value: (B, T, N, H) [batch, seq, heads, head_dim].
    mask: optional (B, 1|N, Tq, Tk) additive-compatible boolean mask
    (True = attend).  The TPU build's analog of the reference's
    interleaved_matmul attention pair.
    """
    def f(*args):
        q, k, v = args[:3]
        m = args[3] if len(args) > 3 else None
        return sdpa_raw(q, k, v, m, scale=scale, causal=causal)

    args = (query, key, value) + ((mask,) if mask is not None else ())
    return apply_op(f, *args, name="dot_product_attention")


_export(dot_product_attention)


def div_sqrt_dim(data, **kwargs):
    """Reference contrib ``_contrib_div_sqrt_dim``: x / sqrt(last_dim)."""
    return apply_op(lambda a: a / np.sqrt(a.shape[-1]), data,
                    name="div_sqrt_dim")


_export(div_sqrt_dim, aliases=("_contrib_div_sqrt_dim",))


def _mxu_einsum(spec, da_spec, db_spec):
    """Dtype-preserving two-operand einsum for low-precision inputs:
    f32 MXU accumulation, outputs AND cotangents downcast to the first
    operand's dtype — same rationale as nn_ops._mxu_matmul (the plain
    pet+astype pattern upcasts every backward contraction to f32xf32).
    ``da_spec``/``db_spec`` are the transpose einsums over (g, other)
    and (g, first) respectively."""
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, a, b,
                          preferred_element_type=np.float32).astype(
                              a.dtype)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        g = g.astype(a.dtype)
        ga = jnp.einsum(da_spec, g, b,
                        preferred_element_type=np.float32).astype(a.dtype)
        gb = jnp.einsum(db_spec, g, a,
                        preferred_element_type=np.float32).astype(b.dtype)
        return ga, gb

    f.defvjp(fwd, bwd)
    return f


# module-level: stable function identity for XLA program caching
_QK_EINSUM = _mxu_einsum("tbnh,sbnh->bnts",
                         "bnts,sbnh->tbnh",
                         "bnts,tbnh->sbnh")
_VALATT_EINSUM = _mxu_einsum("bnts,sbnh->tbnh",
                             "tbnh,sbnh->bnts",
                             "tbnh,bnts->sbnh")


def _low_precision(x):
    from .registry import accum_dtype

    return accum_dtype(x.dtype) is not None


def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1, **kwargs):
    """Reference contrib op: projected interleaved QKV (T, B, 3*E) →
    attention scores (B*heads, T, T) — kept for GluonNLP-script parity;
    new code should use dot_product_attention."""
    def f(qkv):
        t, b, e3 = qkv.shape
        e = e3 // 3
        h = e // heads
        qkv = qkv.reshape(t, b, heads, 3, h)
        q = qkv[:, :, :, 0]
        k = qkv[:, :, :, 1]
        q = q / np.sqrt(h)
        if _low_precision(qkv):
            scores = _QK_EINSUM(q, k)
        else:
            scores = jnp.einsum("tbnh,sbnh->bnts", q, k,
                                preferred_element_type=np.float32)
        return scores.reshape(b * heads, t, t).astype(qkv.dtype)

    return apply_op(f, queries_keys_values, name="interleaved_selfatt_qk")


_export(interleaved_matmul_selfatt_qk,
        aliases=("_contrib_interleaved_matmul_selfatt_qk",))


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                      heads=1, **kwargs):
    """Reference contrib op: attention (B*heads, T, T) x interleaved V →
    (T, B, E)."""
    def f(qkv, att):
        t, b, e3 = qkv.shape
        e = e3 // 3
        h = e // heads
        v = qkv.reshape(t, b, heads, 3, h)[:, :, :, 2]
        att = att.reshape(b, heads, t, t)
        if _low_precision(qkv) and _low_precision(att):
            # both operands already low-precision -> keep the backward
            # einsums in that dtype too.  A mixed caller (f32 softmax
            # probs x bf16 values — standard stability practice) keeps
            # the full-precision contraction below: rounding the probs
            # to bf16 here would silently degrade the forward.
            out = _VALATT_EINSUM(att, v)
        else:
            out = jnp.einsum("bnts,sbnh->tbnh", att, v,
                             preferred_element_type=np.float32)
        return out.reshape(t, b, e).astype(qkv.dtype)

    return apply_op(f, queries_keys_values, attention,
                    name="interleaved_selfatt_valatt")


_export(interleaved_matmul_selfatt_valatt,
        aliases=("_contrib_interleaved_matmul_selfatt_valatt",))

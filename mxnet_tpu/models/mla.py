"""Latent attention's projections (MLA), written once: what
``models.glm_moe_dsa.GlmMath`` (served, over a paged latent cache and an
indexer's selection) and ``models.joyai_flash.JoyMath`` (trained, every
earlier position read through the flash kernels) both compute around
their attention.  ``ops.latent_cache`` owns the stored row, the absorbed
forms and the expansion of a latent row into a head's key and value
(:func:`~mxnet_tpu.ops.latent_cache.expanded_heads`).

With ``norm`` an RMSNorm with a learned weight and rotary pairs
(2i, 2i+1): ``c_q = norm(u W_qa)``; ``q = c_q W_qb``, a head ``nope +
rope`` wide, RoPE on the ``rope`` part; ``[c_kv | k_r] = u W_kva``,
``c_kv = norm(c_kv)``, ``k_r = RoPE(k_r)``, one for all heads; ``[k_nope_h
| v_h] = c_kv W_kvb``; ``y = concat_h(heads) W_o``.  ``p`` holds the
leaves ``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``, ``kv_b``
and ``o``, matrices (out, in).
"""
from __future__ import annotations

from .decoder import apply_rope, rms_norm


def latent_rows(p, u, rope_one, rank, eps):
    """``u`` (.., H) -> (``c_q`` (.., q_lora_rank), ``latent`` (.., rank +
    rope)): the query's low-rank row, and the row a cache keeps, ``[norm(
    c_kv) | RoPE(k_r)]``.  ``rope_one``: the rows' (cos, sin) without a
    head axis."""
    import jax.numpy as jnp

    c_q = rms_norm(u @ p["q_a"].T, p["q_a_norm"], eps)
    kv = u @ p["kv_a"].T
    latent = jnp.concatenate(
        [rms_norm(kv[..., :rank], p["kv_a_norm"], eps),
         apply_rope(kv[..., rank:], *rope_one)], axis=-1)
    return c_q, latent


def query_heads(p, c_q, heads, width):
    """``c_q`` (.., q_lora_rank) -> (.., heads, width): ``c_q W_qb``."""
    return (c_q @ p["q_b"].T).reshape(c_q.shape[:-1] + (heads, width))


def split_query(q, nope, cos, sin):
    """(.., heads, nope + rope) -> (``q_nope``, RoPE(``q_rope``))."""
    return q[..., :nope], apply_rope(q[..., nope:], cos, sin)


def kv_b_halves(kv_b, heads, nope, value, rank):
    """``W_kvb`` (heads * (nope + value), rank) -> (``w_uk`` (heads, nope,
    rank), ``w_uv`` (heads, value, rank))."""
    kv_b = kv_b.reshape(heads, nope + value, rank)
    return kv_b[:, :nope], kv_b[:, nope:]


def output(p, heads):
    """(.., heads, value) -> (.., H): ``concat_h(heads) W_o``."""
    return heads.reshape(heads.shape[:-2] + (-1,)) @ p["o"].T

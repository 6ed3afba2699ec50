"""The latent cache: what a layer with latent attention and a learned
selection keeps a token, how it is stored, and the attention that reads
only the keys a query selects.

Reference: NONE (the reference predates LLM serving).  A latent layer
keeps two rows a token in the slots' block tables:

- the **latent row** ``[c_kv | k_r]``: the compressed keys-and-values
  (``rank`` values, after their norm) and the one rotary key all heads
  share (``rope`` values, after RoPE).  ``rank + rope`` (512 + 64 = 576)
  is 4.5 rows of 128 lanes; a stored row is **padded with zeros to whole
  lane rows** (:func:`stored_width`: 640), so one gather of a row brings
  both parts, a query laid out the same way (:func:`absorbed_query`)
  meets it in one product, and the padding multiplies zeros.  The bytes
  of the padding are the pool's and are counted as stored
  (:func:`bytes_per_block`);
- the **index key**: the selection's key (``index_dim`` = 128 values,
  one lane row), what a later query's indexer scores.

Both pools are block pools of ONE head row, ``(num_blocks, 1,
block_size, lanes)``: the K/V pool's layout with ``Hkv`` = 1, so the
block mechanics (the prefill scatter, where a decode call's rows go,
the row write, the gathered view) are ``ops.paged_attention``'s own
functions.  The index key, the scoring, the exact selection and a
prefill's tiles are ``ops.sparse_select``'s, shared with the K/V layers
that select; this module adds what is the latent kind's alone: the row
format and the attention over the selected rows.  No other module pads
a latent row or knows which lanes hold what.

One meaning, two forms of the attention (``score_h[t, s] = (q_nope_h[t]
. k_nope_h[s] + q_rope_h[t] . k_r[s]) * scale`` over ``s`` in the
selected set, float32 softmax, ``o_h = sum_s p_h v_h``):

- the **absorbed** form the served programs run: ``W_UK`` is folded
  into the query (``q_lat_h = q_nope_h W_UK_h``), scored against the
  latent rows themselves, and ``sum_s p c_kv[s]`` is taken through
  ``W_UV``.  A step gathers its selected rows through the block table
  (:func:`selected_attention`: work is the selected rows', whatever
  the context's length); a prefill tile attends its causal extent of
  rows, which lie in order, under the selection's mask
  (:func:`masked_attention`: a gather of 2,048 rows a query costs more
  than the rows it saves up to 28k keys, PERF.md PR 32);
- :func:`plain_attention`, the **expanded** form: keys and values of
  every position expanded through ``W_kvb``, masked to the selected set.
  The plain form the fast one is held to (tests/, tests_tpu/), and what
  a whole-sequence forward outside the server runs.

The selection (``sparse_select.index_scores``, then ``select`` for a
step's indices or ``select_mask`` for a prefill tile's mask: one set to
the bit) is exact: the ``topk`` visible positions of largest score, a
tie to the earlier position, all of them while fewer are visible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import paged_attention, sparse_select
from .sparse_select import (scatter_rows, stored_width,  # noqa: F401
                            to_lanes as _to_lanes, write_rows)

#: heads whose ``(heads, tile, extent)`` float32 scores a prefill tile
#: holds at a time (:func:`masked_attention`): 0.23 GB at an extent of
#: 28k
HEAD_GROUP = 16


def pool_shapes(num_blocks, block_size, latent_dim, index_dim):
    """(the latent pool's shape, the index-key pool's) as stored."""
    return ((int(num_blocks), 1, int(block_size), stored_width(latent_dim)),
            sparse_select.index_pool_shape(num_blocks, block_size,
                                           index_dim))


def bytes_per_block(block_size, latent_dim, index_dim, itemsize):
    """Bytes one block of one latent layer holds, as stored: the padded
    latent rows and the index keys."""
    return int(block_size) * int(itemsize) * stored_width(latent_dim) \
        + sparse_select.index_bytes_per_block(block_size, index_dim,
                                              itemsize)


def absorbed_query(q_lat, q_rope, lanes):
    """A query in the stored row's layout: ``q_lat`` (.., H, rank) the
    no-position part already through ``W_UK``, ``q_rope`` (.., H, rope)
    after RoPE -> (.., H, lanes), zeros where the row is padding."""
    return _to_lanes(jnp.concatenate([q_lat, q_rope], axis=-1), lanes)


def _absorb(q_nope, q_rope, w_uk, lanes):
    return absorbed_query(jnp.einsum("...hd,hdr->...hr", q_nope, w_uk),
                          q_rope, lanes)


def masked_attention(q_nope, q_rope, stored, chosen, w_uk, w_uv, scale):
    """The absorbed form under a mask: ``stored`` (B, T, lanes) every
    latent row of the extent as stored, ``chosen`` (B, Q, T) the
    selected set; what is not chosen meets probability 0.  A prefill
    tile's form: one product of all the tile's heads against rows that
    lie in order, ``HEAD_GROUP`` heads' scores at a time.  Arithmetic as
    :func:`selected_attention` -> (B, Q, H, dv)."""
    rank = w_uk.shape[-1]
    q = _absorb(q_nope, q_rope, w_uk, stored.shape[-1])
    b, nq, nh, lanes = q.shape
    hg = HEAD_GROUP if nh % HEAD_GROUP == 0 else nh

    def heads(qg):
        s = jnp.einsum("bqhw,btw->bhqt", qg, stored,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(stored.dtype)
        return jnp.einsum("bhqt,btr->bqhr", p, stored[..., :rank],
                          preferred_element_type=jnp.float32).astype(q.dtype)

    ctx = lax.map(heads, jnp.moveaxis(
        q.reshape(b, nq, nh // hg, hg, lanes), 2, 0))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(b, nq, nh, rank)
    return jnp.einsum("...hr,hvr->...hv", ctx, w_uv)


def selected_attention(q_nope, q_rope, rows, valid, w_uk, w_uv, scale):
    """The absorbed form over gathered rows: ``q_nope`` (.., H, dn) is
    folded through ``w_uk`` (H, dn, rank) and laid beside ``q_rope``
    (.., H, rope) as a stored row is (:func:`absorbed_query`); ``rows``
    (.., k, lanes) the selected latent rows as stored, ``valid`` (..,
    k).  Operands in the rows' dtype, float32 scores and softmax, the
    probabilities cast for the second product, float32 accumulation;
    ``sum_s p[s] c_kv[s]`` then goes through ``w_uv`` (H, dv, rank) ->
    (.., H, dv) in the query's dtype."""
    rank = w_uk.shape[-1]
    q = _absorb(q_nope, q_rope, w_uk, rows.shape[-1])
    s = jnp.einsum("...hw,...kw->...hk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[..., None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    ctx = jnp.einsum("...hk,...kr->...hr", p, rows[..., :rank],
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return jnp.einsum("...hr,hvr->...hv", ctx, w_uv)


def expanded_heads(latent, w_uk, w_uv):
    """The expansion of logical latent rows ``(B, T, rank + rope)`` into
    every head's key and value: ``(k_nope (B, T, H, dn), v (B, T, H, dv),
    k_r (B, T, rope))``, ``k_r`` one for all heads.  What the plain form
    below and a trainer's attention over whole sequences both start
    from."""
    rank = w_uk.shape[-1]
    c_kv, k_r = latent[..., :rank], latent[..., rank:]
    k_nope = jnp.einsum("btr,hdr->bthd", c_kv, w_uk)
    v = jnp.einsum("btr,hvr->bthv", c_kv, w_uv)
    return k_nope, v, k_r


def plain_attention(q_nope, q_rope, latent, chosen, w_uk, w_uv, scale):
    """The expanded form, plain: ``q_nope`` (B, Q, H, dn), ``q_rope``
    (B, Q, H, rope), ``latent`` (B, T, rank + rope) logical rows,
    ``chosen`` (B, Q, T) bool the selected set, ``w_uk`` (H, dn, rank)
    and ``w_uv`` (H, dv, rank) the two halves of ``W_kvb`` -> (B, Q, H,
    dv).  Keys and values of every position are expanded and every
    score computed; what is not chosen meets probability 0."""
    k_nope, v, k_r = expanded_heads(latent, w_uk, w_uv)
    s = (jnp.einsum("bqhd,bthd->bhqt", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,btd->bhqt", q_rope, k_r,
                      preferred_element_type=jnp.float32)) * scale
    s = jnp.where(chosen[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqt,bthv->bqhv", p, v,
                      preferred_element_type=jnp.float32).astype(q_nope.dtype)


def window_attention(q_nope, q_rope, latent_pool, win, idx, valid,
                     w_uk, w_uv, scale):
    """A step's attention over the selected rows alone: ``q_nope`` (S,
    H, dn) and ``q_rope`` (S, H, rope) of each slot's token, ``idx`` /
    ``valid`` (S, k) from ``sparse_select.window_select``; each selected
    position's row is fetched through the slot's block table -> (S, H,
    dv)."""
    with jax.named_scope("mla_selected_attention"):
        rows = paged_attention.selected_rows(latent_pool, win, idx)
        return selected_attention(q_nope, q_rope, rows, valid, w_uk, w_uv,
                                  scale)


def causal_attention(make_query, latent, index_keys, per_row, lengths,
                     topk, w_uk, w_uv, scale, finish):
    """A prefill's selection and attention, in tiles of query rows
    (``sparse_select.causal_tiles``: the loop, the scoring and the
    selection's mask).  ``latent`` (B, T, rank + rope) and
    ``index_keys`` (B, T, D) the sequence's own logical rows (row ``t``
    sees ``s <= t``); ``per_row`` a tuple of arrays ``(B or 1, T, ..)``
    that ``make_query`` turns, a tile at a time, into ``(q_nope (B, Q,
    H, dn), q_rope (B, Q, H, rope), q_idx (B, Q, J, D), w_idx (B, Q,
    J))``; ``finish`` takes a tile's heads ``(B, Q, H, dv)`` to what
    the caller keeps of them ``(B, Q, out)``.

    A tile attends its causal extent's rows under the selection's mask
    (:func:`masked_attention`): rows that lie in order, against a
    gather of 2,048 rows a query (3.6 ms a tile on the v5e, PERF.md PR
    32) -> (B, T, out)."""
    stored = _to_lanes(latent, stored_width(latent.shape[-1]))

    def make(*part):
        q_nope, q_rope, q_idx, w_idx = make_query(*part)
        return (q_nope, q_rope), q_idx, w_idx

    def attend(queries, chosen, extent):
        with jax.named_scope("mla_selected_attention"):
            heads = masked_attention(*queries, stored[:, :extent], chosen,
                                     w_uk, w_uv, scale)
        return finish(heads)

    return sparse_select.causal_tiles(make, index_keys, per_row, lengths,
                                      topk, attend)


def plain_causal_attention(make_query, latent, index_keys, per_row, topk,
                           w_uk, w_uv, scale, finish):
    """:func:`causal_attention`'s meaning in the plain form, every row
    at once: the whole ``(T, T)`` scores, the selection as a mask, the
    expanded attention under it.  For sequences short enough to hold
    that (a whole-sequence forward outside the server; the tests)."""
    t = latent.shape[1]
    q_nope, q_rope, q_idx, w_idx = make_query(*per_row)
    scores = sparse_select.index_scores(q_idx, w_idx, index_keys)
    cols = jnp.arange(t)
    idx, valid = sparse_select.select(
        scores, cols[None, :] <= cols[:, None], topk)
    return finish(plain_attention(
        q_nope, q_rope, latent, sparse_select.chosen_mask(idx, valid, t),
        w_uk, w_uv, scale))

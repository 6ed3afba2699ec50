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
functions and this module adds what is the latent kind's alone: the row
format, the scoring, the exact selection and the attention over the
selected rows.  No other module pads a latent row or knows which lanes
hold what.

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

The selection (:func:`index_scores`, then :func:`select` for a step's
indices or :func:`select_mask` for a prefill tile's mask: one set to
the bit) is exact: the ``topk`` visible positions of largest score, a
tie to the earlier position, all of them while fewer are visible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import paged_attention

#: query rows a prefill scores, selects and attends at a time: the
#: indexer's scores of a tile, ``(tile, heads, L)`` float32 before the
#: heads are summed, are 0.5 GB at 32 heads and L = 32,768; the ``(L,
#: L)`` array is never whole
QUERY_TILE = 128

#: a prefill's tiles are grouped by position, this many rows a group, and
#: a group reads the keys up to its own end only (the causal extent):
#: tile ``i`` has no use for a key past ``(i + 1) * QUERY_TILE``
KEY_EXTENT = 4096

#: heads whose ``(heads, tile, extent)`` float32 scores a prefill tile
#: holds at a time (:func:`masked_attention`): 0.23 GB at an extent of
#: 28k
HEAD_GROUP = 16

_LANES = 128


def stored_width(latent_dim):
    """Lanes of a stored latent row: ``latent_dim`` padded up to whole
    rows of 128."""
    return -(-int(latent_dim) // _LANES) * _LANES


def pool_shapes(num_blocks, block_size, latent_dim, index_dim):
    """(the latent pool's shape, the index-key pool's) as stored."""
    return ((int(num_blocks), 1, int(block_size), stored_width(latent_dim)),
            (int(num_blocks), 1, int(block_size), stored_width(index_dim)))


def bytes_per_block(block_size, latent_dim, index_dim, itemsize):
    """Bytes one block of one latent layer holds, as stored: the padded
    latent rows and the index keys."""
    return int(block_size) * int(itemsize) \
        * (stored_width(latent_dim) + stored_width(index_dim))


def _to_lanes(a, lanes):
    """``a`` (.., width) zero-padded to ``lanes`` on its last axis."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, lanes - a.shape[-1])])


def _stored(rows, lanes):
    """Logical rows ``(.., n, width)`` as a pool of ``lanes`` stores
    them: zero-padded, under the one head row."""
    return _to_lanes(rows, lanes)[..., None, :, :]


def scatter_rows(pool, rows, flat_idx):
    """The prefill's hand-over: ``rows`` (KB, Lp, width) logical latent
    rows (or index keys) of KB prompts into the blocks ``flat_idx``
    (``paged_attention.scatter_rows``: sentinel ids drop)."""
    return paged_attention.scatter_rows(
        pool, _stored(rows, pool.shape[-1]), flat_idx)


def write_rows(pool, win, rows):
    """A step's new rows ``(S, width)``, one a slot, written in place at
    the window's addresses (``paged_attention.write_rows``)."""
    return paged_attention.write_rows(
        pool, win, _stored(rows[:, None, :], pool.shape[-1]))


def absorbed_query(q_lat, q_rope, lanes):
    """A query in the stored row's layout: ``q_lat`` (.., H, rank) the
    no-position part already through ``W_UK``, ``q_rope`` (.., H, rope)
    after RoPE -> (.., H, lanes), zeros where the row is padding."""
    return _to_lanes(jnp.concatenate([q_lat, q_rope], axis=-1), lanes)


def index_scores(q_idx, w_idx, keys):
    """The indexer: ``I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])``.
    ``q_idx`` (B, Q, J, D), ``w_idx`` (B, Q, J) with the published
    scales already in it, ``keys`` (B, T, D') whose first D lanes are
    the key.  Products on the operands' dtype with float32 accumulation,
    the sum over heads in float32 -> (B, Q, T) float32."""
    s = jnp.einsum("bqjd,btd->bqjt", q_idx, keys[..., :q_idx.shape[-1]],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bqjt,bqj->bqt", jax.nn.relu(s),
                      w_idx.astype(jnp.float32))


def select(scores, visible, topk):
    """The exact selection: of each row of ``scores`` (.., T) the
    ``topk`` positions of largest score among those ``visible`` (..,
    T), a tie to the earlier position (``lax.top_k`` lists equal values
    by index), all of the visible while they are fewer -> (``idx`` (..,
    k) int32, ``valid`` (.., k) bool), ``k = min(topk, T)``; an entry
    that is not valid names no position (a row sees fewer than k)."""
    k = min(int(topk), scores.shape[-1])
    vals, idx = lax.top_k(jnp.where(visible, scores, -jnp.inf), k)
    return idx.astype(jnp.int32), vals > -jnp.inf


def select_mask(scores, visible, topk):
    """:func:`select`'s set as a mask (.., T) bool, without a sort: the
    value of the ``topk``-th largest visible score by bisection on the
    ordered integer image of float32 (32 counts a row), then, among the
    positions that tie with it, the earliest by a bisection on position.
    The same set to the bit as :func:`select`'s, for a reader that
    attends under a mask instead of gathering."""
    t = scores.shape[-1]
    visible = jnp.broadcast_to(visible, scores.shape)
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # monotone in the score, as unsigned; 0 where not visible (below the
    # image of every float, -inf's too)
    key = jnp.where(bits >= 0, bits, bits ^ 0x7fffffff)
    key = lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)
    key = jnp.where(visible, key, jnp.uint32(0))
    lead = scores.shape[:-1] + (1,)

    def value(_, c):
        lo, hi = c                  # the largest v with count(key >= v) >= k
        mid = lo + (hi - lo) // 2 + ((hi - lo) & 1)
        ok = (key >= mid).sum(-1, keepdims=True) >= topk
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    least, _ = lax.fori_loop(0, 32, value, (
        jnp.zeros(lead, jnp.uint32), jnp.full(lead, 0xffffffff, jnp.uint32)))
    above, tied = key > least, key == least
    room = topk - above.sum(-1, keepdims=True)
    pos = jnp.arange(t, dtype=jnp.int32)

    def place(_, c):
        lo, hi = c                  # the smallest p with that many ties <= p
        mid = lo + (hi - lo) // 2
        ok = (tied & (pos <= mid)).sum(-1, keepdims=True) >= room
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    last, _ = lax.fori_loop(0, max(1, (t - 1).bit_length()), place, (
        jnp.zeros(lead, jnp.int32), jnp.full(lead, t - 1, jnp.int32)))
    # (a row that sees fewer than topk: the value found is below every
    # score and the ties are the positions it does not see)
    return (above | (tied & (pos <= last))) & visible


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


def chosen_mask(idx, valid, t):
    """A selection as a mask: ``idx`` / ``valid`` (.., k) -> (.., T)
    bool, for the plain form."""
    k = idx.shape[-1]
    flat = idx.reshape(-1, k)
    rows = jnp.arange(flat.shape[0])[:, None]
    # a selection names a position once, valid or not
    return jnp.zeros((flat.shape[0], t), bool) \
        .at[rows, flat].set(valid.reshape(-1, k), unique_indices=True) \
        .reshape(idx.shape[:-1] + (t,))


def window_select(q_idx, w_idx, index_pool, win, topk):
    """A step's selection through the block table: ``q_idx`` (S, J, D)
    and ``w_idx`` (S, J) of each slot's one new token (whose own key is
    in the pool already), the visible positions ``t <= pos`` -> (``idx``
    (S, k) positions, ``valid``).  The keys are read through the
    window's clamped table (the gather path's view): garbage behind a
    sentinel entry sits at positions that are not visible."""
    with jax.named_scope("dsa_scoring"):
        keys = paged_attention.gathered_view(index_pool, win.gat, 1)[:, 0]
        scores = index_scores(q_idx[:, None], w_idx[:, None], keys)[:, 0]
    with jax.named_scope("dsa_selection"):
        return select(scores, win.mask[:, 0, 0], topk)


def window_attention(q_nope, q_rope, latent_pool, win, idx, valid,
                     w_uk, w_uv, scale):
    """A step's attention over the selected rows alone: ``q_nope`` (S,
    H, dn) and ``q_rope`` (S, H, rope) of each slot's token, ``idx`` /
    ``valid`` (S, k) from :func:`window_select`; each selected
    position's row is fetched through the slot's block table -> (S, H,
    dv)."""
    with jax.named_scope("mla_selected_attention"):
        nb, _, bs, lanes = latent_pool.shape
        blk = jnp.take_along_axis(win.gat, idx // bs, axis=1)
        rows = latent_pool.reshape(nb * bs, lanes)[blk * bs + idx % bs]
        return selected_attention(q_nope, q_rope, rows, valid, w_uk, w_uv,
                                  scale)


def causal_attention(make_query, latent, index_keys, per_row, lengths,
                     topk, w_uk, w_uv, scale, finish):
    """A prefill's selection and attention, in tiles of query rows so
    that no ``(L, L)`` array is whole.  ``latent`` (B, T, rank + rope)
    and ``index_keys`` (B, T, D) the sequence's own logical rows (row
    ``t`` sees ``s <= t``); ``per_row`` a tuple of arrays ``(B or 1, T,
    ..)`` that ``make_query`` turns, a tile at a time, into ``(q_nope
    (B, Q, H, dn), q_rope (B, Q, H, rope), q_idx (B, Q, J, D), w_idx
    (B, Q, J))``; ``finish`` takes a tile's heads ``(B, Q, H, dv)`` to
    what the caller keeps of them ``(B, Q, out)``.

    A tile scores the index keys of its causal extent (its group's end,
    ``KEY_EXTENT`` rows a group), takes the exact selection as a mask
    (:func:`select_mask`) and attends the extent's rows under it
    (:func:`masked_attention`): rows that lie in order, against a
    gather of 2,048 rows a query (3.6 ms a tile on the v5e, PERF.md PR
    32).  Tiles wholly past every row's ``lengths`` (B,) are not
    computed and read as zeros -> (B, T, out)."""
    b, t = latent.shape[:2]
    tile = min(QUERY_TILE, t)
    if t % tile:
        raise ValueError(f"a prefill of {t} rows is not whole tiles of "
                         f"{tile}")
    stored = _to_lanes(latent, stored_width(latent.shape[-1]))

    def one(i, extent):
        at = i * tile
        part = tuple(lax.dynamic_slice_in_dim(a, at, tile, axis=1)
                     for a in per_row)
        q_nope, q_rope, q_idx, w_idx = make_query(*part)
        with jax.named_scope("dsa_scoring"):
            scores = index_scores(q_idx, w_idx, index_keys[:, :extent])
        with jax.named_scope("dsa_selection"):
            rows_at = at + jnp.arange(tile, dtype=jnp.int32)
            chosen = select_mask(
                scores, jnp.arange(extent)[None, :] <= rows_at[:, None],
                topk)
        with jax.named_scope("mla_selected_attention"):
            heads = masked_attention(q_nope, q_rope, stored[:, :extent],
                                     chosen, w_uk, w_uv, scale)
        return finish(heads)

    first = jax.eval_shape(lambda i: one(i, tile), jnp.int32(0))
    out = jnp.zeros((b, t) + first.shape[2:], first.dtype)
    live_tiles = -(-jnp.max(lengths).astype(jnp.int32) // tile)
    for lo in range(0, t, KEY_EXTENT):
        extent = min(lo + KEY_EXTENT, t)

        def body(i, out, extent=extent):
            return lax.dynamic_update_slice_in_dim(out, one(i, extent),
                                                   i * tile, axis=1)

        out = lax.fori_loop(
            lo // tile, jnp.clip(live_tiles, lo // tile, extent // tile),
            body, out)
    return out


def plain_causal_attention(make_query, latent, index_keys, per_row, topk,
                           w_uk, w_uv, scale, finish):
    """:func:`causal_attention`'s meaning in the plain form, every row
    at once: the whole ``(T, T)`` scores, the selection as a mask, the
    expanded attention under it.  For sequences short enough to hold
    that (a whole-sequence forward outside the server; the tests)."""
    t = latent.shape[1]
    q_nope, q_rope, q_idx, w_idx = make_query(*per_row)
    scores = index_scores(q_idx, w_idx, index_keys)
    cols = jnp.arange(t)
    idx, valid = select(scores, cols[None, :] <= cols[:, None], topk)
    return finish(plain_attention(q_nope, q_rope, latent,
                                  chosen_mask(idx, valid, t), w_uk, w_uv,
                                  scale))

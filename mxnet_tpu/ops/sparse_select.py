"""The learned selection: what a layer whose queries read only the keys
an indexer selects shares, whatever it keeps a token beside the index
key.

Reference: NONE (the reference predates LLM serving).  Two cache kinds
select (``models.decoder.CacheSpec``): the ``"latent"`` kind keeps a
latent row a token (``ops.latent_cache``: the row's format and the
absorbed attention), a ``"kv"`` layer with ``index_dim`` keeps K and V
rows (``ops.paged_attention``: the pools' format).  Both keep an
**index key** a token in the slots' block tables, and this module is
the one home of everything about it:

- the index-key pool: a block pool of ONE head row, ``(num_blocks, 1,
  block_size, lanes)``, the K/V pool's layout with ``Hkv`` = 1, so the
  block mechanics are ``ops.paged_attention``'s own functions; a key
  narrower than a 128-lane row (64 values) is **padded with zeros to a
  whole lane row** (:func:`stored_width`: at a minor dimension of 64
  the device would put the blocks minor-most and re-lay the pool around
  every use), and the padding's bytes are the pool's and counted as
  stored (:func:`index_bytes_per_block`);
- the scoring (:func:`index_scores`), the exact selection as indices
  (:func:`select`, a step's) or as a mask (:func:`select_mask`, a
  prefill tile's: one set to the bit), :func:`chosen_mask`;
- a step's selection through the block table (:func:`window_select`):
  the index keys are read a chunk of blocks at a time, up to the
  longest live slot's position and no further, whatever the table's
  width;
- a prefill's tiles of query rows over their causal extent
  (:func:`causal_tiles`): the loop, the scoring and the mask, with the
  attention under the mask the caller's (latent rows in the absorbed
  form, or K/V rows: :func:`gqa_masked_attention`);
- grouped-query attention over K/V rows under a selection: over
  gathered rows (:func:`gqa_selected_attention`, a step's), under a
  mask over rows in order (:func:`gqa_masked_attention`, a prefill
  tile's), and the plain whole-sequence form both are held to
  (:func:`kv_plain_causal_attention`).

The selection is exact: the ``topk`` visible positions of largest
score, a tie to the earlier position, all of them while fewer are
visible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import paged_attention
from .attention import masked_attention

#: query rows a prefill scores, selects and attends at a time: the
#: indexer's scores of a tile, ``(tile, heads, L)`` float32 before the
#: heads are summed, are 0.5 GB at 32 heads and L = 32,768; the ``(L,
#: L)`` array is never whole
QUERY_TILE = 128

#: a prefill's tiles are grouped by position, this many rows a group, and
#: a group reads the keys up to its own end only (the causal extent):
#: tile ``i`` has no use for a key past ``(i + 1) * QUERY_TILE``
KEY_EXTENT = 4096

#: positions of index keys a step scores at a time (:func:`window_select`):
#: a step walks the slots' blocks in chunks of this many positions up to
#: the longest live slot's, so its reads follow the contexts and not the
#: table's width (``max_length``)
SCORE_CHUNK = 2048

_LANES = 128


def stored_width(width):
    """Lanes of a stored row: ``width`` padded up to whole rows of 128."""
    return -(-int(width) // _LANES) * _LANES


def index_pool_shape(num_blocks, block_size, index_dim):
    """The index-key pool as stored: one head row, whole lane rows."""
    return (int(num_blocks), 1, int(block_size), stored_width(index_dim))


def index_bytes_per_block(block_size, index_dim, itemsize):
    """Bytes one block of one layer's index keys holds, as stored."""
    return int(block_size) * int(itemsize) * stored_width(index_dim)


def to_lanes(a, lanes):
    """``a`` (.., width) zero-padded to ``lanes`` on its last axis."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, lanes - a.shape[-1])])


def _stored(rows, lanes):
    """Logical rows ``(.., n, width)`` as a one-head-row pool of
    ``lanes`` stores them: zero-padded, under the one head row."""
    return to_lanes(rows, lanes)[..., None, :, :]


def scatter_rows(pool, rows, flat_idx):
    """The prefill's hand-over into a one-head-row pool: ``rows`` (KB,
    Lp, width) logical rows (index keys, or latent rows) of KB prompts
    into the blocks ``flat_idx`` (``paged_attention.scatter_rows``:
    sentinel ids drop)."""
    return paged_attention.scatter_rows(
        pool, _stored(rows, pool.shape[-1]), flat_idx)


def write_rows(pool, win, rows):
    """A step's new rows ``(S, width)``, one a slot, written in place at
    the window's addresses (``paged_attention.write_rows``)."""
    return paged_attention.write_rows(
        pool, win, _stored(rows[:, None, :], pool.shape[-1]))


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
    window's clamped table (garbage behind a sentinel entry sits at
    positions that are not visible), ``SCORE_CHUNK`` positions of every
    slot at a time, up to the longest LIVE slot's position: what lies
    past it is visible to no live slot and is neither read nor scored
    (a vacant slot's stale cursor reads as not scored: nobody reads its
    row)."""
    _, _, bs, lanes = index_pool.shape
    s, mb = win.gat.shape
    cb = max(1, min(mb, SCORE_CHUNK // bs))          # blocks a chunk
    chunks = -(-mb // cb)
    # (a table that is not whole chunks: block 0 again, behind every
    # visible position)
    gat = jnp.pad(win.gat, ((0, 0), (0, chunks * cb - mb)))
    with jax.named_scope("dsa_scoring"):
        longest = jnp.max(jnp.where(win.live, win.first, 0)) + 1

        def chunk(c, scores):
            ids = lax.dynamic_slice_in_dim(gat, c * cb, cb, axis=1)
            keys = index_pool[ids][:, :, 0].reshape(s, cb * bs, lanes)
            part = index_scores(q_idx[:, None], w_idx[:, None], keys)[:, 0]
            return lax.dynamic_update_slice_in_dim(scores, part,
                                                   c * cb * bs, axis=1)

        scores = lax.fori_loop(
            0, -(-longest // (cb * bs)), chunk,
            jnp.full((s, chunks * cb * bs), -jnp.inf, jnp.float32))
    with jax.named_scope("dsa_selection"):
        return select(scores[:, :mb * bs], win.mask[:, 0, 0], topk)


def causal_tiles(make, index_keys, per_row, lengths, topk, attend):
    """A prefill's selection and attention, in tiles of query rows so
    that no ``(L, L)`` array is whole.  ``index_keys`` (B, T, D) the
    sequence's own logical index keys (row ``t`` sees ``s <= t``);
    ``per_row`` a tuple of arrays ``(B or 1, T, ..)`` that ``make``
    turns, a tile at a time, into ``(queries, q_idx (B, Q, J, D), w_idx
    (B, Q, J))``; ``attend(queries, chosen (B, Q, extent), extent)`` is
    the kind's attention of a tile under the selection's mask over the
    first ``extent`` rows -> what the caller keeps of it ``(B, Q,
    out)``.

    A tile scores the index keys of its causal extent (its group's end,
    ``KEY_EXTENT`` rows a group) and takes the exact selection as a
    mask (:func:`select_mask`): rows that lie in order under a mask,
    against a gather of 2,048 rows a query (PERF.md PRs 32, 51).  A
    tile whose last row sees no more than ``topk`` positions reads all
    it sees and scores nothing.  Tiles wholly past every row's
    ``lengths`` (B,) are not computed and read as zeros -> (B, T,
    out)."""
    b, t = index_keys.shape[:2]
    tile = min(QUERY_TILE, t)
    if t % tile:
        raise ValueError(f"a prefill of {t} rows is not whole tiles of "
                         f"{tile}")

    def by_score(q_idx, w_idx, visible, extent):
        with jax.named_scope("dsa_scoring"):
            scores = index_scores(q_idx, w_idx, index_keys[:, :extent])
        with jax.named_scope("dsa_selection"):
            return select_mask(scores, visible, topk)

    def all_visible(_q_idx, _w_idx, visible, extent):
        return jnp.broadcast_to(visible, (b, tile, extent))

    def one(i, extent, choose):
        at = i * tile
        part = tuple(lax.dynamic_slice_in_dim(a, at, tile, axis=1)
                     for a in per_row)
        queries, q_idx, w_idx = make(*part)
        rows_at = at + jnp.arange(tile, dtype=jnp.int32)
        visible = jnp.arange(extent)[None, :] <= rows_at[:, None]
        return attend(queries, choose(q_idx, w_idx, visible, extent), extent)

    first = jax.eval_shape(lambda i: one(i, tile, all_visible),
                           jnp.int32(0))
    out = jnp.zeros((b, t) + first.shape[2:], first.dtype)
    live_tiles = -(-jnp.max(lengths).astype(jnp.int32) // tile)
    unscored = int(topk) // tile      # tiles that end at or before topk
    for lo in range(0, t, KEY_EXTENT):
        extent = min(lo + KEY_EXTENT, t)
        a, z = lo // tile, extent // tile
        split = min(z, max(a, unscored))
        for t0, t1, choose in ((a, split, all_visible), (split, z, by_score)):
            if t0 == t1:
                continue

            def body(i, out, extent=extent, choose=choose):
                return lax.dynamic_update_slice_in_dim(
                    out, one(i, extent, choose), i * tile, axis=1)

            out = lax.fori_loop(t0, jnp.clip(live_tiles, t0, t1), body, out)
    return out


# -- grouped-query attention over K/V rows under a selection ------------------

def gqa_selected_attention(q, k_rows, v_rows, valid):
    """Attention over gathered rows, a step's form: ``q`` (S, H, hd);
    ``k_rows`` / ``v_rows`` (S, k, Hkv * hd) the selected positions'
    rows, every KV head of a position side by side (head ``n`` in lanes
    ``[n * hd, (n + 1) * hd)``); ``valid`` (S, k).  Query head ``h``
    reads KV head ``h // (H / Hkv)``.  Operands in the rows' dtype,
    float32 scores and softmax, the probabilities cast for the second
    product, float32 accumulation -> (S, H, hd) in ``q``'s dtype."""
    s, nh, hd = q.shape
    k = k_rows.shape[1]
    hkv = k_rows.shape[-1] // hd
    qg = q.reshape(s, hkv, nh // hkv, hd)
    kr, vr = (r.reshape(s, k, hkv, hd) for r in (k_rows, v_rows))
    sc = jnp.einsum("sngd,sknd->sngk", qg, kr,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    sc = jnp.where(valid[:, None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(vr.dtype)
    return jnp.einsum("sngk,sknd->sngd", p, vr,
                      preferred_element_type=jnp.float32) \
        .astype(q.dtype).reshape(s, nh, hd)


def gqa_masked_attention(q, k, v, chosen):
    """Attention under a mask over rows that lie in order, a prefill
    tile's form: ``q`` (B, H, Q, hd) and ``k`` / ``v`` (B, Hkv, T, hd)
    heads-major, ``chosen`` (B, Q, T) the selected set; what is not
    chosen meets probability 0.  One KV head's ``(H / Hkv, Q, T)``
    float32 scores at a time (117 MB at 8 heads, 128 rows and 28k
    keys), no repeated K/V.  Arithmetic as
    :func:`gqa_selected_attention` -> (B, Q, H, hd)."""
    b, nh, nq, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, nh // hkv, nq, hd)

    def group(args):
        qh, kh, vh = args           # (B, g, Q, hd), (B, T, hd) twice
        s = jnp.einsum("bgqd,btd->bgqt", qh, kh,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        s = jnp.where(chosen[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
        return jnp.einsum("bgqt,btd->bqgd", p, vh,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    ctx = lax.map(group, tuple(jnp.moveaxis(a, 1, 0) for a in (qg, k, v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, nq, nh, hd)


def kv_causal_attention(q, k, v, q_idx, w_idx, index_keys, lengths, topk):
    """A K/V layer's prefill under its selection, in :func:`causal_tiles`:
    ``q`` (B, H, T, hd), ``k`` / ``v`` (B, Hkv, T, hd) after RoPE,
    ``q_idx`` (B, T, J, D), ``w_idx`` (B, T, J), ``index_keys`` (B, T,
    D) -> the context (B, T, H, hd), zeros past the live tiles."""
    b, nh, t, hd = q.shape

    def attend(qt, chosen, extent):
        with jax.named_scope("gqa_selected_attention"):
            ctx = gqa_masked_attention(jnp.swapaxes(qt, 1, 2),
                                       k[:, :, :extent], v[:, :, :extent],
                                       chosen)
        return ctx.reshape(b, -1, nh * hd)

    out = causal_tiles(lambda qt, qi, wi: (qt, qi, wi), index_keys,
                       (jnp.swapaxes(q, 1, 2), q_idx, w_idx), lengths,
                       topk, attend)
    return out.reshape(b, t, nh, hd)


def kv_plain_causal_attention(q, k, v, q_idx, w_idx, index_keys, topk):
    """:func:`kv_causal_attention`'s meaning in the plain form, every
    row at once: the whole ``(T, T)`` scores, the sorted selection as a
    mask, ``ops.attention.masked_attention`` under it (K/V repeated for
    the query heads).  For sequences short enough to hold that (a
    whole-sequence forward outside the server; the tests) -> (B, T, H,
    hd)."""
    t = q.shape[2]
    cols = jnp.arange(t)
    idx, valid = select(index_scores(q_idx, w_idx, index_keys),
                        cols[None, :] <= cols[:, None], topk)
    return masked_attention(q, k, v, chosen_mask(idx, valid, t)[:, None]) \
        .transpose(0, 2, 1, 3)

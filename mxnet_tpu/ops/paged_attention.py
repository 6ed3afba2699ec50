"""Paged decode attention: one Pallas TPU kernel that reads the KV pool
in place, through the block table.

Reference: NONE (the reference predates LLM serving).  The serving step
(``models.decoder.PagedDecoder._step_blocks_impl``, through a
``StepView``) used to gather every slot's whole ``(Hkv, MB*bs, hd)``
logical view out of the pool, repeat it ``H / Hkv`` times for GQA and
attend over all of it, whatever the slots held.  This kernel never
builds a view:

- ``tables`` and the per-slot schedule ride as scalar-prefetch operands
  (SMEM), the pools stay in HBM (``memory_space=pl.ANY``);
- a grid step is one slot; it walks the slot's blocks a chunk
  (``blocks_per_chunk`` blocks) at a time, each block one async copy of
  all KV heads (``(Hkv, bs, hd)``, contiguous in the pool's layout)
  into a double-buffered VMEM scratch.  The next chunk — of this slot,
  or the first of the next slot that has any — is in flight while the
  current one is computed;
- work is bounded by ``lengths[s]``: blocks past ``ceil(lengths[s] /
  bs)`` are neither fetched nor computed, the last block is masked by
  position, and a row that starts with the sentinel (a vacant slot)
  costs no KV read and yields zeros.  Sentinel ids never index the
  pool;
- the ``H / Hkv`` query heads of a KV head meet that head's keys once:
  no repeat, in HBM or in VMEM;
- arithmetic as ``ops.attention.masked_attention``: operands in the pool's dtype,
  float32 scores and online-softmax state (running max, sum,
  accumulator), the probabilities cast to the pool's dtype for the
  second product, float32 accumulation, output in ``q``'s dtype.

The speculative verify is the same kernel with ``K`` query columns a
slot: column ``j`` sees ``lengths[s] + j`` rows, and the ``K * H / Hkv``
rows of a KV head share its keys.  A block decoder's pass (``block``,
static) is that window without a causal order inside it: every column
sees the whole window, ``lengths[s] + K - 1`` rows.

Heads narrower than a 128-lane row (``head_dim`` 64) are stored packed:
``pack = 128 // head_dim`` consecutive KV heads share one lane row, the
pool is ``(num_blocks, Hkv // pack, block_size, pack * head_dim)``
(:func:`pack_rows` / :func:`unpack_rows`: the same bytes, and the
row-major layout a minor dimension of 128 gets; at 64 the device would
put the blocks minor-most and re-lay the whole pool around every use).
The kernel is the same: to it a lane row is one KV head of 128.  The
wrapper lays the queries of a lane row's heads block-diagonally (head
``p``'s query in lanes ``[p * head_dim, (p + 1) * head_dim)``, zeros
elsewhere), so one product against the key tile gives every head's
scores and each output row's own lanes are its context.  Half of the
matrix unit's work multiplies zeros; decode attention is bound by
bytes.

Rows of an owned block past the slot's length are fetched with their
block and meet probability 0, as in the gather path: both count on the
pool holding finite numbers (it is born zero and only ever written with
K/V rows).

This module owns the pool's storage format, all of it:
:func:`pool_shape`, the prefill scatter and the prefix gather
(:func:`scatter_rows`, :func:`gather_rows`), where a pass of a stack run
several times keeps its rows (:func:`pass_blocks`,
:func:`scatter_pass_rows`), where a decode call's rows
go and what its queries see (:func:`window`), the row write
(:func:`write_rows`) and the decode attention, kernel or gather
(:func:`window_attention`), and the fetch of the rows a selection names
(:func:`selected_rows`: a selecting layer's pools keep every KV head of
a token in one stored row, ``pack`` = ``Hkv``).  No
other module indexes a pool's axes,
computes with ``pack`` or writes a pool with ``mode="drop"``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .attention import masked_attention

#: reviewed signature budget (mxlint T15): the jit at the foot of this
#: module is inlined into the step or verify program that calls it and
#: compiles nothing of its own there; called alone (tests_tpu/, tools/)
#: it is one program per operand shapes
__compile_signatures__ = {
    "paged_decode_attention":
        "0 inside a serving program; 1 per (operand shapes, "
        "blocks_per_chunk) when called alone",
}

#: blocks fetched and computed per inner step.  On the v5e, 8 KV heads of
#: 128 in bf16, blocks of 16 (PERF.md, PR 25): 16 blocks a chunk read
#: 260-350 GB/s, 32 read 360 (58 slots of 100-500 tokens) to 550 (full
#: slots), 64 read 310 to 680: a chunk computes its unused tail, so the
#: widest loses on short slots.  At 32, K and V double-buffered take
#: 4 MiB of VMEM.  At 8 KV heads of 64, two to a lane row (PR 27), the
#: order is the same at half the bytes a block: 196 / 252 / 226 GB/s on
#: 115 slots of 100-500 tokens, 266 / 376 / 453 on 128 full slots.
BLOCKS_PER_CHUNK = 32


def _sublane_tile(dtype):
    """Rows of one (sublane, 128-lane) tile of ``dtype``: 8 for 4-byte
    types, 16 for bf16, 32 for 1-byte types."""
    return 32 // np.dtype(dtype).itemsize


def applicable(platform, mesh, head_dim, num_kv_heads, block_size, dtype):
    """Whether the kernel can stand in for the gather path, from what
    the caller observes: the platform its pool lives on, the engine's
    mesh (a tp-sharded pool would need a ``shard_map`` wrapper: it keeps
    the gather path), and the shapes Mosaic tiles without padding.
    Returns ``pack``, the KV heads the pool then stores to a 128-lane
    row (1 at heads of 128 and wider), or 0: the gather path and the
    unpacked pool."""
    pack = max(1, 128 // head_dim)
    ok = (platform == "tpu" and mesh is None
          and head_dim >= 64 and (pack * head_dim) % 128 == 0
          and num_kv_heads % pack == 0
          and block_size % _sublane_tile(dtype) == 0)
    return pack if ok else 0


def pool_shape(num_blocks, num_kv_heads, head_dim, block_size, pack):
    """A K or V pool as stored: ``pack`` KV heads to a row of
    ``pack * head_dim`` lanes (``pack`` from :func:`applicable`, 1 on
    the gather path: one head a row)."""
    return (num_blocks, num_kv_heads // pack, block_size, pack * head_dim)


def pack_rows(a, pack):
    """Logical K/V rows ``(.., Hkv, n, hd)`` as a packed pool stores
    them, ``(.., Hkv // pack, n, pack * hd)``: head ``r * pack + p`` in
    lanes ``[p * hd, (p + 1) * hd)`` of head row ``r``."""
    if pack == 1:
        return a
    *lead, hkv, n, hd = a.shape
    return jnp.moveaxis(a.reshape(*lead, hkv // pack, pack, n, hd), -3, -2) \
        .reshape(*lead, hkv // pack, n, pack * hd)


def unpack_rows(a, pack):
    """:func:`pack_rows` undone: ``(.., R, n, pack * hd)`` ->
    ``(.., R * pack, n, hd)``."""
    if pack == 1:
        return a
    *lead, r, n, lanes = a.shape
    return jnp.moveaxis(a.reshape(*lead, r, n, pack, lanes // pack), -2, -3) \
        .reshape(*lead, r * pack, n, lanes // pack)


def gathered_view(pool, gat, pack):
    """The gather path's dense view of a stored pool through block ids
    ``gat`` (S, MB), the sentinel already clamped: (S, Hkv, MB * bs,
    hd), one head a row whatever ``pack`` the pool was stored with."""
    s, mb = gat.shape
    _, rows, bs, lanes = pool.shape
    return unpack_rows(pool[gat].transpose(0, 2, 1, 3, 4)
                       .reshape(s, rows, mb * bs, lanes), pack)


def scatter_rows(pool, rows, flat_idx):
    """The prefill's hand-over: ``rows`` (KB, Hkv, Lp, hd) raw K or V of
    KB prompts, chunked into ``ceil(Lp / bs)`` block-sized pieces a
    prompt and written at ``flat_idx`` (KB * nbp,) physical block ids —
    sentinel ids (== num_blocks) drop, covering vacant batch rows AND
    chunks past a short prompt's allocation."""
    _, hkv, bs, lanes = pool.shape                  # as stored
    kb, lp = rows.shape[0], rows.shape[2]
    nbp = flat_idx.shape[0] // kb
    pad = ((0, 0), (0, 0), (0, nbp * bs - lp), (0, 0))
    chunks = jnp.pad(pack_rows(rows, lanes // rows.shape[-1]), pad) \
        .reshape(kb, hkv, nbp, bs, lanes) \
        .transpose(0, 2, 1, 3, 4) \
        .reshape(kb * nbp, hkv, bs, lanes)
    return pool.at[flat_idx].set(chunks, mode="drop")


def pass_blocks(block_ids, t, num_blocks, passes):
    """Where pass ``t`` of a stack run ``passes`` times a token keeps
    its rows, in a pool of ``passes x num_blocks`` blocks: the slots'
    ``block_ids`` (any shape; ``num_blocks`` and above: the sentinel)
    moved ``t x num_blocks`` on, the sentinel kept a drop (the larger
    pool's own: ``passes x num_blocks``).  So one block table addresses
    every pass, and the window, the row write and the kernel are called
    as they are."""
    return jnp.where(block_ids < num_blocks, block_ids + t * num_blocks,
                     passes * num_blocks)


def scatter_pass_rows(pool, rows, flat_idx):
    """:func:`scatter_rows` of a stack run several times: ``rows``
    (passes, KB, Hkv, Lp, hd), a prefill's raw K or V pass by pass, into
    a pool of ``passes x num_blocks`` blocks, pass ``t``'s at
    :func:`pass_blocks` of ``flat_idx`` (KB * nbp,), in one scatter."""
    passes = rows.shape[0]
    num_blocks = pool.shape[0] // passes
    idx = jnp.concatenate([pass_blocks(flat_idx, t, num_blocks, passes)
                           for t in range(passes)])
    return scatter_rows(pool, rows.reshape((-1,) + rows.shape[2:]), idx)


def gather_rows(pool, block_ids, pack):
    """Dense copies of whole blocks: ``block_ids`` (KB, NBP) physical
    ids in logical order, sentinel-padded -> (KB, Hkv, NBP * bs, hd),
    unpacked (the radix cache's prefix, for the suffix prefill).
    Sentinel entries clamp to garbage rows the reader's mask never
    exposes."""
    return gathered_view(pool, jnp.minimum(block_ids, pool.shape[0] - 1),
                         pack)


class Window(NamedTuple):
    """Where a decode call's new K/V rows go in a pool and what its
    queries see of it: built once a program (:func:`window`), shared by
    its layers, whose pools are all of one shape."""

    blk: jax.Array      #: physical block of each new row; sentinel: dropped
    off: jax.Array      #: the row's offset in its block
    heads: jax.Array    #: the stored head rows, the scatter's middle index
    tables: jax.Array   #: (S, MB) block ids in logical order
    first: jax.Array    #: (S,) position of each slot's first query column
    gat: jax.Array      #: the table, sentinel clamped; None under the kernel
    mask: jax.Array     #: (S, 1, K, T) what a column sees (gather path)
    live: jax.Array     #: the columns a request owns: no vacant slot's
    block: bool = False  #: static: every column sees the whole window


def window(pool, tables, pos, max_len, paged_kernel, block=False):
    """The :class:`Window` of a decode call over ``tables`` (S, MB),
    vacant entries = ``num_blocks``.  ``pos`` (S,): a step, each slot's
    one new row at ``(tables[s, pos // bs], pos % bs)`` — the sentinel
    id is out of bounds, so vacant slots' writes DROP.  ``pos`` (S, K):
    K columns a slot (the speculative verify), each at its own absolute
    position; with ``block`` (static) the K columns are one block of a
    block decoder and each sees all of them (``t <= pos[:, -1]``).  The
    gather path reads each slot's logical view through a clamped table;
    garbage read through clamped sentinel entries sits at positions the
    mask (``t <= pos``, per column) never exposes."""
    nb, rows, bs, _ = pool.shape                    # as stored
    mb = tables.shape[1]
    t = jnp.arange(mb * bs)
    if pos.ndim == 1:
        mask = (t[None, :] <= pos[:, None])[:, None, None, :]
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)
        off = (pos % bs)[:, None]
        heads = jnp.arange(rows)[None, :]
        first, live = pos, tables[:, 0] < nb
    else:
        sees = pos[:, -1:] if block else pos
        mask = (t[None, None, :] <= sees[:, :, None])[:, None]
        blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, mb - 1),
                                  axis=1)
        # columns past max_len have no legal row: force the sentinel so
        # the scatter drops instead of wrapping into a clamped block
        blk = jnp.where(pos < jnp.int32(max_len), blk, nb)[:, :, None]
        off = (pos % bs)[:, :, None]
        heads = jnp.arange(rows)[None, None, :]
        first = pos[:, 0]
        live = jnp.broadcast_to(tables[:, :1] < nb, pos.shape)
    if paged_kernel:
        return Window(blk, off, heads, tables, first, None, None, live,
                      block)
    return Window(blk, off, heads, tables, first,
                  jnp.minimum(tables, nb - 1), mask, live, block)


def write_rows(pool, win, rows):
    """``rows`` (S, Hkv, K, hd), the call's new K or V after RoPE (K = 1
    for a step), written in place at the window's addresses, whole
    stored rows whatever ``pack``.

    Head row by head row, (block, row, offset) -> ``lanes`` contiguous
    values: a scatter with the heads as a window makes XLA:TPU re-lay
    the whole pool, in and out, every layer.  Rejected columns of a
    verify window need no cleanup: their rows sit beyond the
    rolled-back cursor where the causal mask never exposes them, and
    the next window overwrites them in place (the stale-row
    invariant)."""
    s, _, kk, _ = rows.shape
    nrows, lanes = pool.shape[1], pool.shape[3]
    if win.blk.ndim == 2:
        new = rows[:, :, 0, :].reshape(s, nrows, lanes)
    else:
        new = rows.transpose(0, 2, 1, 3).reshape(s, kk, nrows, lanes)
    return pool.at[win.blk, win.heads, win.off].set(new, mode="drop")


def window_attention(q, k_pool, v_pool, win):
    """Decode attention of ``q`` (S, H, K, hd) over the pools, the
    call's own rows already written: the kernel, bounded by each
    column's position + 1 (a vacant slot's context is then zeros
    instead of attention over clamped garbage; neither is ever read),
    or the gathered view under the window's mask.  -> the context,
    heads beside their channels: (S, K, H, hd), or a step's
    (S, H[, 1], hd)."""
    step = win.blk.ndim == 2
    if win.gat is None:
        return paged_decode_attention(
            q[:, :, 0, :] if step else q.transpose(0, 2, 1, 3),
            k_pool, v_pool, win.tables, win.first + 1, block=win.block)
    pack = k_pool.shape[3] // q.shape[-1]
    kc, vc = (gathered_view(p, win.gat, pack) for p in (k_pool, v_pool))
    ctx = masked_attention(q, kc, vc, win.mask)
    return ctx if step else ctx.transpose(0, 2, 1, 3)


def selected_rows(pool, win, idx):
    """The rows a selection names, and no others: ``idx`` (S, k)
    positions (``ops.sparse_select.window_select``'s), each fetched
    through the slot's block table, whatever the context's length.  The
    pool keeps a token in ONE stored row (a one-head-row pool; a
    selecting K/V layer's pools at ``pack = Hkv``: ``(num_blocks, 1,
    block_size, Hkv * hd)``), so a selected position is one contiguous
    read and not ``Hkv`` reads a block apart -> (S, k, lanes); what the
    rows mean, and the attention over them, is the caller's."""
    nb, rows, bs, lanes = pool.shape
    if rows != 1:
        raise ValueError(
            "a selection reads pools that keep a token in one row; this "
            f"one has {rows} rows")
    blk = jnp.take_along_axis(win.gat, idx // bs, axis=1)
    return pool.reshape(nb * bs, lanes)[blk * bs + idx % bs]


def _schedule(tables, lengths, num_blocks, block_size, chunk):
    """Per-slot walk of the kernel, computed once in XLA on (S, MB)
    integers: ``nblk`` blocks to read (bounded by the longest column's
    ``lengths`` AND by the row's leading non-sentinel entries), ``par``
    the scratch buffer of the slot's first chunk (the chunks of all
    slots alternate between the two buffers) and ``nxt``, where
    ``nxt[0]`` is the first slot with any chunk and ``nxt[s + 1]`` the
    next one after ``s`` (``S`` for none)."""
    s, mb = tables.shape
    owned = jnp.cumprod((tables < num_blocks).astype(jnp.int32),
                        axis=1).sum(axis=1)
    lengths = jnp.clip(lengths, 0, mb * block_size)
    nblk = jnp.minimum(-(-lengths // block_size), owned).astype(jnp.int32)
    nch = -(-nblk // chunk)
    par = ((jnp.cumsum(nch) - nch) % 2).astype(jnp.int32)
    idx = jnp.where(nch > 0, jnp.arange(s, dtype=jnp.int32), s)
    after = lax.cummin(idx, axis=0, reverse=True)
    nxt = jnp.concatenate([after, jnp.full((1,), s, jnp.int32)])
    return nblk, par, nxt


def _kernel(len_ref, nblk_ref, par_ref, nxt_ref, tab_ref,
            q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, k_sem, v_sem, m_ref, l_ref, acc_ref,
            *, chunk, max_blocks, group, scale, block):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    num_slots = pl.num_programs(0)
    _, _, hkv, bs, hd = k_buf.shape
    nblk = nblk_ref[s]
    nch = (nblk + chunk - 1) // chunk

    def transfer(slot, c, buf, wait):
        """Start (or wait for) the K and V copies of every block of
        chunk ``c`` of ``slot`` that the slot reads, into scratch
        buffer ``buf``.  A loop, not ``chunk`` unrolled copies: the
        kernel is traced and lowered at every start of a server."""
        first = c * chunk

        def block(j, carry):
            bid = tab_ref[slot * max_blocks + first + j]
            for hbm, vmem, sem in ((k_hbm, k_buf, k_sem),
                                   (v_hbm, v_buf, v_sem)):
                copy = pltpu.make_async_copy(
                    hbm.at[bid], vmem.at[buf, j], sem.at[buf])
                copy.wait() if wait else copy.start()
            return carry

        lax.fori_loop(0, jnp.minimum(chunk, nblk_ref[slot] - first),
                      block, 0)

    start = functools.partial(transfer, wait=False)
    wait = functools.partial(transfer, wait=True)

    @pl.when(s == 0)
    def _first_step():
        # a chunk's unfetched blocks meet probability 0; what the
        # scratch was born with must not be NaN under that 0
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(s == nxt_ref[0])
    def _first_chunk():
        start(s, 0, par_ref[s])

    @pl.when(nch == 0)
    def _vacant():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(nch > 0)
    def _attend():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # a row of a KV head's tile is (column j, query head g): it sees
        # lengths + j rows of what was fetched (pad rows ride along);
        # the columns of a block decoder's window all see its last row
        col = lax.broadcasted_iota(jnp.int32, (m_ref.shape[1], 1),
                                   0) // group
        if block:
            col = block - 1       # ``block``: the window's columns, or 0
        bound = jnp.minimum(len_ref[s] + col, nblk * bs)
        par = par_ref[s]
        follower = nxt_ref[s + 1]

        def body(c, carry):
            buf = (par + c) % 2

            @pl.when(c + 1 < nch)
            def _():
                start(s, c + 1, 1 - buf)

            @pl.when(jnp.logical_and(c + 1 == nch, follower < num_slots))
            def _():
                start(follower, 0, 1 - buf)

            wait(s, c, buf)
            tpos = c * (chunk * bs) + lax.broadcasted_iota(
                jnp.int32, (1, chunk * bs), 1)
            live = tpos < bound

            # the heads are unrolled (as a loop the kernel ran a fifth
            # slower: their matrix products no longer overlap); the
            # copies above are loops, which costs nothing
            for h in range(hkv):
                k = k_buf[buf, :, h].reshape(chunk * bs, hd)
                v = v_buf[buf, :, h].reshape(chunk * bs, hd)
                sc = lax.dot_general(
                    q_ref[0, h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(live, sc, -jnp.inf)
                m_prev = m_ref[h]
                # every row sees position 0, so the running max is
                # finite from the first chunk on
                m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
                acc_ref[h] = alpha * acc_ref[h] + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            return carry

        lax.fori_loop(0, nch, body, 0)
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                            blocks_per_chunk=BLOCKS_PER_CHUNK,
                            interpret=False, block=False):
    """Decode attention of one new token per slot over a paged KV pool.

    ``q`` (S, H, hd) after RoPE; ``k_pool`` / ``v_pool`` ``(num_blocks,
    Hkv // pack, block_size, pack * hd)`` holding the new token's row
    already (``pack`` is read off the shapes: 1 at heads of 128);
    ``tables`` (S, MB) int32 block ids in logical order, vacant entries
    = ``num_blocks``; ``lengths`` (S,) int32 rows to attend
    (``pos + 1``).  Returns (S, H, hd) in ``q``'s dtype; a slot with no
    owned block yields zeros.

    With ``q`` (S, K, H, hd), the speculative verify's window, column
    ``j`` attends ``lengths + j`` rows; returns (S, K, H, hd).  With
    ``block`` (static) the K columns are a block decoder's block: each
    attends ``lengths + K - 1`` rows, the whole window."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cols = q.shape[1] if q.ndim == 4 else 1
    s, h, hd = q.shape[0], q.shape[-2], q.shape[-1]
    nb, hkv, bs, lanes = k_pool.shape
    pack = lanes // hd
    mb = tables.shape[1]
    # query heads of a lane row, column by column: the kernel's "group"
    g = h // hkv
    chunk = int(min(blocks_per_chunk, mb))
    # the rows of a lane row's score tile are (column, query head);
    # padded up to whole sublane tiles of the operand dtype
    rows = cols * g
    tile = _sublane_tile(q.dtype)
    gp = -(-rows // tile) * tile
    qg = q.reshape(s, cols, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    if pack > 1:
        # block-diagonal: the g / pack query heads of KV head p keep
        # lanes [p * hd, (p + 1) * hd) and are zero in the others'
        own = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
        qg = (qg.reshape(s, hkv, cols, pack, g // pack, 1, hd) * own) \
            .reshape(s, hkv, cols, g, lanes)
    qg = qg.reshape(s, hkv, rows, lanes)
    if gp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rows), (0, 0)))
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    # nothing to attend means nothing to read, whatever the row owns
    last = jnp.where(lengths > 0, lengths + (cols - 1), 0)
    nblk, par, nxt = _schedule(tables, last, nb, bs, chunk)
    qspec = pl.BlockSpec((1, hkv, gp, lanes), lambda i, *_: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, max_blocks=mb, group=g,
                          scale=1.0 / float(np.sqrt(hd)),
                          block=cols if block else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s,),
            in_specs=[qspec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((2, chunk, hkv, bs, lanes), k_pool.dtype),
                pltpu.VMEM((2, chunk, hkv, bs, lanes), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hkv, gp, 1), jnp.float32),    # running max
                pltpu.VMEM((hkv, gp, 1), jnp.float32),    # running sum
                pltpu.VMEM((hkv, gp, lanes), jnp.float32),  # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((s, hkv, gp, lanes), q.dtype),
        # slots run in order: the copies of a slot's first chunk are
        # started by the slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
    )(lengths, nblk, par, nxt, tables.reshape(-1), qg, k_pool, v_pool)
    out = out[:, :, :rows].reshape(s, hkv, cols, g, lanes)
    if pack > 1:
        # a row's context is in its own KV head's lanes
        out = out.reshape(s, hkv, cols, pack, g // pack, pack, hd)
        out = jnp.stack([out[:, :, :, p, :, p] for p in range(pack)],
                        axis=3)
    return out.reshape(s, hkv, cols, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(q.shape)


#: jitted, so that the layers of a step program share one trace and one
#: Mosaic lowering of the kernel (a third of a second each, every start).
#: The tests run ``_paged_decode_attention`` under the TPU interpreter
#: eagerly: its simulated copies deadlock now and then inside a jit.
paged_decode_attention = jax.jit(
    _paged_decode_attention, static_argnames=("blocks_per_chunk",
                                              "interpret", "block"))

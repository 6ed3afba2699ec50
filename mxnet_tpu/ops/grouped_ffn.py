"""Grouped expert feed-forward: one Pallas TPU kernel that streams each
routed expert's weights and computes only the rows routed to it.

Reference: NONE (the reference has no sparse experts).
``models.moe.routed_ffn``'s other form runs every held expert on every
row under a combine matrix; past some 240 rows a call that product is
bound by operations nobody asked for (PERF.md, PRs 31 and 33).  Here:

- the (row, expert) pairs are listed expert by expert, the pairs no held
  expert computes (another chip's expert, a row no request owns) last
  or not at all; pair ``p`` of the list is a token's row and a float32
  weight.  Groups are NOT padded: a row tile of listed pairs that
  straddles a group boundary is visited once for each expert it holds
  rows of, and a visit keeps the rows of its own expert (``lo <= pair <
  hi``);
- a grid step is one visit ``(expert, row tile)`` at one width tile.
  The visits are listed expert by expert (:func:`_visits`) and
  prefetched as scalars.  SwiGLU separates over the intermediate
  width, ``y = sum_t silu(x Wg[:, t]) * (x Wu[:, t]) @ Wd[t, :]``: an
  expert is walked in tiles of ``wt`` of its width, blocks ``w_gate``
  / ``w_up`` ``(H, wt)`` and ``w_down`` ``(wt, H)`` straight from the
  stacked bank as it lies in HBM, the float32 ``(rows, H)`` product
  summed over the tiles in VMEM.  Where two whole experts fit VMEM
  (``wt`` = the width) the block index changes only when the expert
  does: each touched expert is fetched once, an expert without a row
  never.  Where they do not, a visit re-reads its expert, and the row
  tile grows so that a visit stays bound by its products
  (:func:`tiles`).  Steps past the last visit repeat its indices and
  compute nothing;
- gate, up, ``silu(gate) * up`` and down run inside the visit: the
  ``(rows, wt)`` intermediate never leaves VMEM.  Operands in the
  bank's dtype, float32 accumulation in each product and over the
  width tiles, the pair's weight applied once in float32 to the
  float32 down product;
- the rows cross between the tokens' order and the experts' order in
  kernels, in one of two forms chosen from static shapes
  (:func:`rows_form`), and a row's ``k`` products are summed in float32
  in a fixed order, expert by expert.  **Resident**
  (``grouped_expert_ffn_resident``): where every pair fits one window
  (:func:`window_pairs`) and the call's rows, their float32 sum and the
  kernel's blocks fit VMEM (a served step's hundreds of rows), ``x`` (N,
  H) and the sum (N, H) stay in VMEM for the whole call; XLA sorts the
  ``N x k`` keys (integer work), a visit takes its own pairs' rows out
  of the resident ``x`` by their token, computes them and adds each
  float32 result row into the resident sum at its token; one cast at
  the last step.  No array of ``N x k`` rows of ``H`` exists, in either
  direction.  (Before PR 48 XLA gathered ``x[rows]``, took a float32
  row a pair from the kernel, gathered those back and summed over
  ``k``: 0.15 us a pair, PERF.md.)  **Windowed**: where the pairs exceed
  a window (a prefill of thousands of rows, a trainer's step, over a
  bank that holds a part of the router's experts) only the HELD pairs
  are listed, expert by expert and row by row, from a running count over
  ``(expert, row)`` (no sort), and gathered, visited and written a
  window of them at a time under a loop whose trip count the device
  computes: nothing is dropped at any skew (every pair held takes every
  window);
- a window's rows go back into the tokens' order inside a kernel,
  ``grouped_expert_ffn_rows`` (:func:`_add_rows`), into ONE float32
  ``(N, H)`` sum carried in place.  Inside one expert's group the rows
  ascend and do not repeat, so the pairs of an expert that fall in a
  tile of tokens are one run of the sorted list, its bounds read off
  the listing's own count: a grid step is (token tile, a chunk of a
  run), the token tile stays in VMEM while its runs are added row by
  row, exact float32 adds in a fixed order, and a tile without a pair
  is never opened.  (XLA's scatter-add of the same rows took 0.19-0.22
  us a row of 8 KB and grew faster than the window, PERF.md, PR 46;
  its gather of the rows IN runs near the bytes' time and stays.)
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from ..base import MXNetError

#: reviewed signature budget (mxlint T15): inlined into the step or
#: prefill program that calls it; alone (tests_tpu/, tools/) one
#: program per operand shapes
__compile_signatures__ = {
    "grouped_expert_ffn":
        "0 inside a serving or training program; 1 per (operand shapes, "
        "tiles, window) when called alone (its jits, ``_resident_jit``, "
        "``_held_windows_jit`` and the backward's ``_grouped_bwd_jit``)",
}

#: sorted pairs a visit computes where two whole experts stay in VMEM
#: (see the module text).  An MXU pass costs the same for 8 rows as for
#: 128, so the row tile is 128: fewer visits, each at the price of one.
#: Tiles of 64 and 256 read within 2% of it at every size from 128 to
#: 2,048 rows (PERF.md, PR 31): there the call is bound by the experts'
#: bytes, not by its tiles.
ROW_TILE = 128

#: sorted pairs a visit computes where an expert is walked in width
#: tiles.  A visit then re-reads its expert: ``2 x rows / itemsize``
#: operations a byte, against the v5e's 197e12 / 819e9 = 240, so 256
#: rows are the fewest at which a visit is bound by its products; a
#: wider tile computes more rows of the experts it straddles.  On the
#: v5e (PERF.md, PR 33, ``tools/routed_ffn_bench.py --tiles``; 16 of 256
#: experts of 6,144 x 2,048, 8 a row): the kernel alone over a full
#: window of 5,376 held pairs 6.80 ms at 128 rows a visit, **4.76** at
#: 256, 5.01 at 384, 5.75 at 512 (width tiles of 256: two of 512 beside
#: 512 rows do not fit); a width tile of 256 reads 4.77 beside 512's
#: 4.76.  One layer with its routing at 16,384 rows: 21.3 / 19.2 / 19.7
#: / 21.1 ms.
ROW_TILE_WALKED = 256

#: fewest rows a call at which ``routed_ffn`` takes this form.  Every
#: expert on every row costs ``max(N x bank operations / peak, bank
#: bytes / bandwidth)`` and turns compute-bound at 2 x 197e12 / 819e9 =
#: 240 rows whatever the bank's shape; this form costs the touched
#: experts' bytes and, since its rows cross orders in VMEM, next to
#: nothing a pair (0.004 us; 0.15 while XLA sorted, gathered and summed
#: them, which is what put the crossing "near 290 rows" in PR 31).
#: Re-measured on the v5e (PERF.md, PR 48, ``tools/routed_ffn_bench.py``:
#: one layer with its routing, ms a call, every expert / this form, at
#: 128 / 256 / 384 / 512 rows): 128 experts of 768, 8 a row: 1.74 / 1.74,
#: 1.90 / 1.76, 2.72 / 1.80, 3.42 / 1.82; 64 of 1,536, 4 a row: 1.75 /
#: 1.74, 1.87 / 1.77, 2.66 / 1.77, 3.41 / 1.78; 128 of 512, 10 a row:
#: 1.22 / 1.13, 1.38 / 1.26, 1.92 / 1.30, 2.44 / 1.35; 128 ``"relu2"``
#: experts of 2,688 over latent rows of 1,024, 22 a row: 2.05 / 2.00,
#: 2.26 / 2.13, 3.30 / 2.21, 4.28 / 2.27.  This form now reads lower at
#: every size for every bank (by 0.2-7% at 128 rows, 6-9% at 256), so
#: the evidence says 128.  The constant stays 384 in PR 48 all the
#: same: lowering it hands the steps of ``lfm2_24b`` (128 rows),
#: ``qwen3_next`` (256) and ``nemotron3_super`` (128) to the kernel, a
#: pair of each on the chip has to show it first, and no chip was free
#: for them (PERF.md section 7, ROADMAP S7(3): the next PR's first run).
GROUPED_MIN_ROWS = 384

#: VMEM a call may ask for: a v5e core has 128 MiB
_VMEM_CAP = 100 * 2 ** 20

#: most bytes of float32 output rows a window holds: 16,384 pairs at a
#: hidden size of 2,048, 5,376 (whole tiles of 256) at 6,144.  A call
#: whose ``N x k`` pairs all fit may keep its rows in VMEM
#: (:func:`rows_form`); past that only the HELD pairs are listed,
#: a window of this size at a time, and the last window's unused rest is
#: neither computed nor added (the kernels stop at the listed pairs).
#: What a window costs whatever it holds is its listing, its gathers and
#: a pass of ``grouped_expert_ffn_rows`` over the token tiles its pairs
#: touch, some 0.8 ms; what used to cap it, XLA's scatter-add growing
#: faster than the window (0.85 us a row of 24 KB at 1,792 rows, 1.89 at
#: 2,816: PR 33's 1,792), went with PR 46.  On the v5e (PERF.md, PR 46,
#: ``tools/routed_ffn_bench.py --tiles ..@window``): the trained layer
#: (16,384 rows, 8 of 256 experts, 16 of 768 held, some 8,200 held
#: pairs; ms a layer forward + backward with its routing at windows of
#: 1,792 / 4,096 / 8,192 / 16,384) 32.9 / 28.5 / 26.6 / **25.9**; GLM-5's
#: (16 of 256 experts of 6,144 x 2,048, every row live; ms a layer forward
#: with its routing at windows of 1,792 / **5,376** / 10,752) 6.8 / 6.4 /
#: 7.0 at 8,192 rows, 13.6 / 11.3 / 11.0 at 16,384, 32.6 / 23.0 / 20.9 at
#: 32,768 (PR 33: 10.45 / 12.99 / 24.00, every expert on every row 55.2 /
#: 112.3 / 224.4).  At the trained layer the one window of 16,384 holds
#: some 8,200 listed pairs, so ``x[rows]``, ``dy[rows]`` and the float32
#: buffers are half rows nobody listed (the kernels stop at the listed
#: pairs; the gathers and the buffers do not): measured cheaper, 25.9
#: against 26.6, than a second window's listing and pass.
_ONE_WINDOW_BYTES = 2 ** 27

#: sorted pairs a step of ``grouped_expert_ffn_rows`` brings to VMEM.  A
#: run (one expert's pairs inside one token tile) holds ``token tile x k
#: / router's experts`` pairs under even routing: 1,024 x 8 / 256 = 32 at
#: the trained cell, 512 x 8 / 256 = 16 at GLM-5's long prefill, whose
#: narrower token tile (:func:`token_rows`) answers its wider rows.  A
#: run begins anywhere in a chunk, so it takes two or three steps of 16
#: at either; a longer chunk fetches more rows past a run's ends, a
#: shorter one takes more steps.  Not swept on the chip: the call reads
#: 0.97 ms where its bytes are 0.4 (PERF.md, PR 46), and what is left is
#: the row-by-row adds, not the chunk.  Two blocks of 16 float32 rows are
#: 256 KB at 2,048 wide, 768 KB at 6,144: nothing beside the token tiles.
_CHUNK = 16


def _rows_vmem_bytes(hidden, tt):
    """What ``grouped_expert_ffn_rows`` keeps in VMEM: the token tile
    as it came and as it leaves, and the chunk of pairs, two of each."""
    return 2 * (2 * tt + _CHUNK) * hidden * 4


def token_rows(rows, hidden):
    """Rows of the float32 sum a step of ``grouped_expert_ffn_rows``
    keeps in VMEM, from static shapes alone: the largest power of two
    whose blocks take half the cap (1,024 at a hidden size of 2,048, 512
    at 6,144: a wider tile has longer runs, so fewer steps), all the
    rows where they are fewer."""
    tt = 8
    while _rows_vmem_bytes(hidden, 2 * tt) <= _VMEM_CAP // 2:
        tt *= 2
    return min(tt, rows)


def _vmem_bytes(hidden, wt, itemsize, tm, walked=False):
    """What a call keeps in VMEM: two width tiles of an expert (the one
    computed, the one in flight; whole experts where ``wt`` is the
    width), two tiles each of rows, weights and output, the visit's
    float32 intermediates, and where an expert is ``walked`` in several
    tiles the float32 sum over them."""
    expert = 3 * hidden * wt * itemsize
    blocks = 2 * tm * (hidden * itemsize + 128 * 4 + hidden * 4)
    temps = tm * (3 * wt + 2 * hidden) * 4
    return 2 * expert + blocks + temps + walked * tm * hidden * 4


def tiles(hidden, width, itemsize=2):
    """``(row tile, width tile)`` of a call over experts ``(hidden,
    width)``, from static shapes alone, or None where nothing fits:
    whole experts and ``ROW_TILE`` rows where two experts fit the cap
    (the kernel of PR 31 to the letter), else ``ROW_TILE_WALKED`` rows
    and the widest multiple of 128 that divides the width and fits."""
    if hidden % 128 or width % 128:
        return None
    if _vmem_bytes(hidden, width, itemsize, ROW_TILE) <= _VMEM_CAP:
        return ROW_TILE, width
    for wt in range(width - 128, 0, -128):
        if width % wt == 0 and _vmem_bytes(
                hidden, wt, itemsize, ROW_TILE_WALKED, True) <= _VMEM_CAP:
            return ROW_TILE_WALKED, wt
    return None


def window_pairs(rows, k, hidden, tm):
    """Sorted pairs a window of a call of ``rows`` rows of ``k`` experts
    holds, whole row tiles: every pair where their float32 rows would
    take no more than ``_ONE_WINDOW_BYTES`` (the served calls of 512
    rows), else as many as do."""
    pairs = -(-rows * k // tm) * tm
    return min(pairs, max(1, _ONE_WINDOW_BYTES // (4 * hidden) // tm) * tm)


def _resident_vmem_bytes(rows, hidden, wt, itemsize, tm, walked=False):
    """What the resident form keeps in VMEM: beside the kernel's own
    blocks (:func:`_vmem_bytes`, whose row and output tiles are here the
    visit's gathered rows and weighted products), every row of the call
    in float32 as it came (two buffers) and as it leaves (two, in the
    bank's dtype), and the float32 sum."""
    return _vmem_bytes(hidden, wt, itemsize, tm, walked) \
        + rows * hidden * (2 * 4 + 2 * itemsize + 4)


def rows_form(rows, k, hidden, width, itemsize=2, tiling=None, window=None):
    """Where a call of ``rows`` rows of ``k`` experts each over experts
    ``(hidden, width)`` crosses between the tokens' order and the
    experts', from static shapes alone: ``"resident"``, in VMEM inside
    the one kernel, where every pair fits one window and the rows, their
    float32 sum and the kernel's blocks fit ``_VMEM_CAP`` (the served
    calls of 512 rows: 42 of 100 MiB at 128 experts of 768 x 2,048), else
    ``"kernel"``, a window of held pairs at a time through
    ``grouped_expert_ffn_rows``.  ``tiling`` ``(row tile, width tile)``
    and ``window`` default to what the shapes say."""
    tm, wt = tiling or tiles(hidden, width, itemsize) or (ROW_TILE, width)
    win = window or window_pairs(rows, k, hidden, tm)
    fits = _resident_vmem_bytes(rows, hidden, wt, itemsize, tm,
                                wt < width) <= _VMEM_CAP
    return "resident" if -(-rows * k // tm) * tm <= win and fits else "kernel"


def applicable(platform, mesh, rows, k, held, hidden, width, itemsize=2):
    """Whether ``routed_ffn`` evaluates ``rows`` rows of ``k`` experts
    each over a bank of ``held`` experts ``(hidden, width)`` through
    this kernel, from what the caller observes: the platform, the mesh
    (a sharded bank would need a ``shard_map`` wrapper: it keeps every
    expert on every row) and static shapes: lanes of 128, tiles that
    fit VMEM (:func:`tiles`), and enough rows a call that the other
    form is bound by its operations and not by the bank's bytes."""
    return (platform == "tpu" and mesh is None
            and rows >= GROUPED_MIN_ROWS and 1 <= k <= held
            and tiles(hidden, width, itemsize) is not None)


def check_kind(kind, w_gate):
    """An expert's ``kind`` beside its banks: ``"swiglu"``, ``(silu(x W_g)
    * (x W_u)) W_d`` over three banks, or ``"relu2"``, ``relu(x W_u)^2
    W_d`` over two (``w_gate`` None)."""
    if kind not in ("swiglu", "relu2") or (kind == "relu2") != (w_gate is None):
        raise MXNetError(f"expert kind {kind!r} with w_gate "
                         f"{'absent' if w_gate is None else 'given'}: "
                         "\"swiglu\" takes three banks, \"relu2\" two")


def _groups(key, held):
    """Where each held expert's group starts and ends in the sorted
    expert ids ``key``."""
    # counted, not searched: a binary search is a loop of gathers on
    # the device, 1 ms a window where counting is one fused pass
    ends = jnp.searchsorted(key, jnp.arange(held, dtype=jnp.int32),
                            side="right", method="compare_all") \
        .astype(jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]]), ends


def _visits(key, held, tm):
    """The kernel's walk over sorted expert ids ``key`` (W,), a window
    of the sorted list or all of it, ``held`` and more for a pair no
    held expert computes: visit ``v`` is expert ``eid[v]`` on row tile
    ``tid[v]``, whose own pairs are ``lo[v] <= p < hi[v]``; ``total``
    visits, listed expert by expert and so tile by tile too, the static
    rest repeating the last one."""
    mp = key.shape[0]
    starts, ends = _groups(key, held)
    sizes = ends - starts
    spans = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    vend = jnp.cumsum(spans)
    total = vend[-1]
    # a tile boundary or a group boundary starts every visit but one
    v = jnp.arange(mp // tm + held - 1, dtype=jnp.int32)
    v = jnp.minimum(v, jnp.maximum(total - 1, 0))
    eid = jnp.minimum(jnp.searchsorted(vend, v, side="right",
                                       method="compare_all"),
                      held - 1).astype(jnp.int32)
    tid = starts[eid] // tm + (v - (vend - spans)[eid])
    return eid, tid.astype(jnp.int32), starts[eid], ends[eid], total[None]


def _visit_product(x, t, gate_ref, up_ref, down_ref, acc_ref, finish):
    """A visit's rows ``x`` (tm, H) through its expert's blocks at width
    tile ``t``: ``finish(y)`` takes the float32 (tm, H) down product, once
    a visit, at its last width tile."""
    from jax.experimental import pallas as pl

    if gate_ref is None:
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        act = jnp.square(jnp.maximum(u, 0.0)).astype(x.dtype)
    else:
        g = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        act = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    y = jnp.dot(act, down_ref[0], preferred_element_type=jnp.float32)
    if not acc_ref:                 # the whole width in one tile
        finish(y)
        return
    # an expert walked in width tiles: the float32 sum over them
    # stays in VMEM; weight, mask and output once, at the last
    acc, last = acc_ref[0], pl.num_programs(1) - 1

    @pl.when(t == 0)
    def _first():
        acc[...] = y

    @pl.when(jnp.logical_and(t > 0, t < last))
    def _middle():
        acc[...] += y

    @pl.when(t == last)
    def _last():
        finish(acc[...] + y)


def _kernel(eid_ref, tid_ref, lo_ref, hi_ref, total_ref,
            x_ref, w_ref, *refs, kind="swiglu"):
    """A window's visits over rows gathered for it.  ``refs``: the
    visit's blocks of the bank (``gate``, ``up``, ``down``; a ``"relu2"``
    expert has no gate), the output tile and, where an expert is walked
    in width tiles, the float32 sum."""
    from jax.experimental import pallas as pl

    if kind == "relu2":
        gate_ref, (up_ref, down_ref, o_ref, *acc_ref) = None, refs
    else:
        gate_ref, up_ref, down_ref, o_ref, *acc_ref = refs
    v, t = pl.program_id(0), pl.program_id(1)
    tm = x_ref.shape[0]

    def finish(y):
        y = y * w_ref[...]
        tile = tid_ref[v]
        pair = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = jnp.logical_and(pair >= lo_ref[v], pair < hi_ref[v])
        # the tile's first visit owes the other experts' rows nothing
        # yet: zeros, whatever the buffer held
        opened = jnp.logical_or(v == 0,
                                tid_ref[jnp.maximum(v - 1, 0)] != tile)
        rest = jnp.where(opened, 0.0, o_ref[...])
        o_ref[...] = jnp.where(mine, y, rest)

    @pl.when(v < total_ref[0])
    def _visit():
        _visit_product(x_ref[...], t, gate_ref, up_ref, down_ref, acc_ref,
                       finish)


def _resident_kernel(eid_ref, first_ref, hi_ref, total_ref, rows_ref, ws_ref,
                     x_ref, *refs, kind="swiglu"):
    """The visits of a call whose rows and float32 sum stay in VMEM:
    ``x_ref`` (N, H) float32 every row of the call, ``rows_ref`` each
    listed pair's token and ``ws_ref`` its weight; visit ``v``'s pairs
    are ``first_ref[v] <= p < hi_ref[v]``, ``tm`` at most, one expert's.
    ``refs``: the bank's blocks as :func:`_kernel`'s, the output (N, H),
    then scratch: the visit's rows (tm, H) float32, their products, the
    sum (N, H) and, where an expert is walked in width tiles, the sum
    over them."""
    from jax.experimental import pallas as pl

    if kind == "relu2":
        gate_ref, (up_ref, down_ref, o_ref, xs_ref, y_ref, sum_ref,
                   *acc_ref) = None, refs
    else:
        (gate_ref, up_ref, down_ref, o_ref, xs_ref, y_ref, sum_ref,
         *acc_ref) = refs
    v, t = pl.program_id(0), pl.program_id(1)
    first, hi = first_ref[v], hi_ref[v]

    @pl.when(jnp.logical_and(v == 0, t == 0))
    def _open():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def finish(y):
        y_ref[...] = y

        def add(p, carry):
            # the pair's weight once, in float32, to the float32 product;
            # exact float32 adds in the listing's order, expert by expert
            sum_ref[pl.ds(rows_ref[p], 1), :] += \
                ws_ref[p] * y_ref[pl.ds(p - first, 1), :]
            return carry

        lax.fori_loop(first, hi, add, 0)

    @pl.when(v < total_ref[0])
    def _visit():
        @pl.when(t == 0)
        def _rows_in():
            def take(p, carry):
                xs_ref[pl.ds(p - first, 1), :] = \
                    x_ref[pl.ds(rows_ref[p], 1), :]
                return carry

            lax.fori_loop(first, hi, take, 0)

        # the scratch's other rows are whatever it held: a row's product
        # is its own, and nobody reads theirs
        _visit_product(xs_ref[...].astype(up_ref.dtype), t, gate_ref, up_ref,
                       down_ref, acc_ref, finish)

    @pl.when(jnp.logical_and(v == pl.num_programs(0) - 1,
                             t == pl.num_programs(1) - 1))
    def _close():
        o_ref[...] = sum_ref[...].astype(o_ref.dtype)


def _window(x, rows, key, ws, bank, tm, wt, interpret):
    """One window of the sorted list: ``rows`` (W,) the token of each
    pair, ``key`` (W,) its expert (sorted), ``ws`` (W,) its weight ->
    the pairs' float32 products (W, H); a pair nobody computed lies in
    a tile that may never have been written.  ``bank``: ``(w_gate,
    w_up, w_down)``, ``w_gate`` None for a ``"relu2"`` expert."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_gate, w_up, w_down = bank
    relu2 = w_gate is None
    held, h, i = w_up.shape
    nw = i // wt
    eid, tid, lo, hi, total = _visits(key, held, tm)

    def at_rows(v, t, eid, tid, *_):
        return tid[v], 0

    def width_tile(v, t, total):
        # a step past the last visit keeps the tile that is there
        return jnp.where(v < total[0], t, nw - 1)

    def gate_up(v, t, eid, tid, lo, hi, total):
        return eid[v], 0, width_tile(v, t, total)

    def down(v, t, eid, tid, lo, hi, total):
        return eid[v], width_tile(v, t, total), 0

    need = _vmem_bytes(h, wt, np.dtype(w_up.dtype).itemsize, tm, nw > 1)
    return pl.pallas_call(
        functools.partial(_kernel, kind="relu2") if relu2 else _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(eid.shape[0], nw),
            in_specs=[pl.BlockSpec((tm, h), at_rows),
                      pl.BlockSpec((tm, 1), at_rows)]
            + [pl.BlockSpec((1, h, wt), gate_up)] * (1 if relu2 else 2)
            + [pl.BlockSpec((1, wt, h), down)],
            out_specs=pl.BlockSpec((tm, h), at_rows),
            # the float32 sum over an expert's width tiles
            scratch_shapes=[pltpu.VMEM((tm, h), jnp.float32)]
            if nw > 1 else []),
        out_shape=jax.ShapeDtypeStruct((key.shape[0], h), jnp.float32),
        # visits run in order: an expert's weights stay while it is
        # the next visit's too, a row tile's output until it is left
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, need + 8 * 2 ** 20)),
        name="grouped_expert_ffn",
        interpret=interpret,
    )(eid, tid, lo, hi, total, x[rows], ws[:, None],
      *(() if relu2 else (w_gate,)), w_up, w_down)


def _keys(idx, held, live):
    """(N, k) int32: each pair's expert counted from the bank's first,
    ``held`` for a pair nobody here computes (another chip's expert, a
    row no request owns)."""
    there = jnp.logical_and(idx >= 0, idx < held)
    if live is not None:
        there = jnp.logical_and(there, live[:, None])
    return jnp.where(there, idx, held).astype(jnp.int32)


def _sorted_pairs(idx, weights, held, live, tm):
    """A resident call's listing: every pair sorted by expert, whole row
    tiles of ``tm``, those no held expert computes last -> each pair's
    token, its expert (``held`` for nobody's) and its float32 weight.
    Integer work on ``N x k`` numbers; inside an expert the rows ascend."""
    n, k = idx.shape
    m = n * k
    mp = -(-m // tm) * tm
    key = jnp.pad(_keys(idx, held, live).reshape(-1), (0, mp - m),
                  constant_values=held)
    key, order = lax.sort((key, jnp.arange(mp, dtype=jnp.int32)),
                          num_keys=1)
    ws = jnp.pad(weights.reshape(-1).astype(jnp.float32), (0, mp - m))[order]
    return jnp.minimum(order // k, n - 1), key, ws


def _own_visits(key, held, tm):
    """The resident kernel's walk over sorted expert ids ``key`` (W,):
    a visit is ``tm`` listed pairs of ONE expert at most, from wherever
    its group stands in the list (the rows are taken by their token, so a
    visit owes the list's row tiles nothing, and a group of up to ``tm``
    pairs is one visit however it lies) -> visit ``v`` is expert
    ``eid[v]`` on the pairs ``first[v] <= p < hi[v]``; ``total`` visits,
    expert by expert, the static rest repeating the last one."""
    mp = key.shape[0]
    starts, ends = _groups(key, held)
    spans = (ends - starts + tm - 1) // tm
    vend = jnp.cumsum(spans)
    total = vend[-1]
    v = jnp.arange(mp // tm + held, dtype=jnp.int32)
    v = jnp.minimum(v, jnp.maximum(total - 1, 0))
    eid = jnp.minimum(jnp.searchsorted(vend, v, side="right",
                                       method="compare_all"),
                      held - 1).astype(jnp.int32)
    first = starts[eid] + (v - (vend - spans)[eid]) * tm
    return eid, first, jnp.minimum(ends[eid], first + tm), total[None]


def _resident_visits(x, rows, key, ws, bank, tm, wt, interpret):
    """The visits over a listing (:func:`_sorted_pairs`) with ``x`` (N, H)
    and the float32 sum in VMEM for the whole call: a visit takes its own
    pairs' rows out of ``x`` by their token, computes them and adds each
    float32 result into the sum at its token, row by row in the
    listing's order; the last step casts the sum -> (N, H)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_gate, w_up, w_down = bank
    n, h = x.shape
    relu2 = w_gate is None
    held, _, i = w_up.shape
    nw = i // wt
    eid, first, hi, total = _own_visits(key, held, tm)

    def whole(v, t, *_):
        return 0, 0

    def width_tile(v, t, total):
        # a step past the last visit keeps the tile that is there
        return jnp.where(v < total[0], t, nw - 1)

    def gate_up(v, t, eid, first, hi, total, *_):
        return eid[v], 0, width_tile(v, t, total)

    def down(v, t, eid, first, hi, total, *_):
        return eid[v], width_tile(v, t, total), 0

    itemsize = np.dtype(w_up.dtype).itemsize
    need = _resident_vmem_bytes(n, h, wt, itemsize, tm, nw > 1)
    return pl.pallas_call(
        functools.partial(_resident_kernel, kind="relu2" if relu2
                          else "swiglu"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(eid.shape[0], nw),
            in_specs=[pl.BlockSpec((n, h), whole)]
            + [pl.BlockSpec((1, h, wt), gate_up)] * (1 if relu2 else 2)
            + [pl.BlockSpec((1, wt, h), down)],
            out_specs=pl.BlockSpec((n, h), whole),
            # a visit's rows and their products, the sum in the tokens'
            # order, the sum over an expert's width tiles
            scratch_shapes=[pltpu.VMEM((tm, h), jnp.float32),
                            pltpu.VMEM((tm, h), jnp.float32),
                            pltpu.VMEM((n, h), jnp.float32)]
            + ([pltpu.VMEM((tm, h), jnp.float32)] if nw > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        # visits run in order: an expert's weights stay while it is the
        # next visit's too, the sum until the last step casts it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_CAP, need + 8 * 2 ** 20)),
        name="grouped_expert_ffn_resident",
        interpret=interpret,
    )(eid, first, hi, total, rows, ws,
      # a one-row slice at a token's index is a float32 array's (a row of
      # packed bf16 is half a sublane); bf16 -> float32 -> bf16 is exact
      x.astype(jnp.float32),
      *(() if relu2 else (w_gate,)), w_up, w_down)


def _resident(x, idx, weights, w_gate, w_up, w_down, live, tm, wt,
              interpret):
    """Every pair in one window: listed by a sort of ``N x k`` keys,
    crossed between the two orders inside the kernel.  Nothing of
    ``N x k`` rows of ``H`` exists."""
    return _resident_visits(
        x, *_sorted_pairs(idx, weights, w_up.shape[0], live, tm),
        (w_gate, w_up, w_down), tm, wt, interpret)


def _held_pairs(idx, held, live, tt):
    """A long prefill's, and every backward's, listing: the pairs HELD
    here alone, expert by expert and row by row.  No sort (XLA's sort of
    2^15 keys and more compiles for half a minute) and nothing of N x k
    rows: ``upto[e * rows + r]`` counts the held pairs up to expert
    ``e``'s row ``r`` (``rows``: N in whole token tiles of ``tt``), so
    sorted pair ``p`` is the first (e, r) with ``upto > p``.  ->
    ``(pairs held, window, runs)``; ``window(w, win)`` names window
    ``w``'s ``win`` pairs: each one's row, its expert (``held`` past the
    last pair) and which of its row's ``k`` choices it is, (win, k)
    bool; ``runs(w, win, c)`` is the walk of :func:`_add_rows` over the
    same window (see there)."""
    n = idx.shape[0]
    tiles_n = -(-n // tt)
    rows_n = tiles_n * tt
    key = _keys(idx, held, live)
    chose = (key[:, :, None] == jnp.arange(held, dtype=jnp.int32)).any(1)
    flat = jnp.pad(chose, ((0, rows_n - n), (0, 0))).T.reshape(-1)
    upto = jnp.cumsum(jnp.pad(flat, (0, -flat.shape[0] % 128)),
                      dtype=jnp.int32)
    # inside one expert's group the rows ascend and do not repeat: the
    # pairs of expert e that fall in token tile T are ONE run of the
    # sorted list, and the count read where the tile ends is its end
    ends = upto[:held * rows_n].reshape(held, tiles_n, tt)[:, :, -1]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              ends.reshape(-1)[:-1]]).reshape(ends.shape)
    starts, ends = starts.T.reshape(-1), ends.T.reshape(-1)   # tile by tile
    upto = upto.reshape(-1, 128)

    def window(w, win):
        p = w * win + jnp.arange(win, dtype=jnp.int32)
        # the entries of ``upto`` that are <= p, counted in two steps
        # (whole lines of 128, then inside the line that is left): a
        # binary search is 19 gathers of a window's length, 2.8 ms
        line = jnp.minimum((upto[:, -1] <= p[:, None]).sum(axis=1),
                           upto.shape[0] - 1)
        at = line * 128 + (upto[line] <= p[:, None]).sum(axis=1)
        at = jnp.minimum(at, held * rows_n - 1).astype(jnp.int32)
        key_w = jnp.where(p < upto[-1, -1], at // rows_n, held)
        rows = jnp.minimum(at % rows_n, n - 1)
        return rows, key_w, key[rows] == key_w[:, None]

    def runs(w, win, c):
        # window ``w``'s part of every run, counted from the window's
        # first pair; a run takes the chunks of ``c`` pairs it touches
        a = jnp.clip(starts - w * win, 0, win)
        b = jnp.clip(ends - w * win, 0, win)
        spans = jnp.where(b > a, (b - 1) // c - a // c + 1, 0)
        vend = jnp.cumsum(spans)
        total = vend[-1]
        # a chunk boundary or a run's first pair starts every step
        i = jnp.arange(win // c + min(win, spans.shape[0]), dtype=jnp.int32)
        i = jnp.minimum(i, jnp.maximum(total - 1, 0))
        run = jnp.minimum(jnp.searchsorted(vend, i, side="right",
                                           method="compare_all"),
                          spans.shape[0] - 1).astype(jnp.int32)
        chunk = a[run] // c + (i - (vend - spans)[run])
        return (run // held, jnp.minimum(chunk, win // c - 1), a[run],
                b[run], total[None])

    return upto[-1, -1], window, runs


def _rows_kernel(tile_ref, chunk_ref, lo_ref, hi_ref, total_ref, rows_ref,
                 src_ref, sum_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    c, tt = src_ref.shape[0], sum_ref.shape[0]
    opened = jnp.logical_or(i == 0,
                            tile_ref[jnp.maximum(i - 1, 0)] != tile_ref[i])

    @pl.when(opened)
    def _open():
        o_ref[...] = sum_ref[...]

    @pl.when(i < total_ref[0])
    def _step():
        first, row0 = chunk_ref[i] * c, tile_ref[i] * tt
        for j in range(c):
            @pl.when(jnp.logical_and(first + j >= lo_ref[i],
                                     first + j < hi_ref[i]))
            def _pair():
                r = rows_ref[first + j] - row0
                o_ref[pl.ds(r, 1), :] += src_ref[pl.ds(j, 1), :]


def _add_rows(total, src, rows, walk, tt, c, interpret):
    """``total[rows[p]] += src[p]`` for the window's listed pairs, in
    place: ``total`` (N, H) float32 the sum in the tokens' order,
    ``src`` (W, H) float32 the window's rows in the experts' order,
    ``rows`` (W,) each pair's token.  ``walk`` (:func:`_held_pairs`'s
    ``runs``) lists the steps token tile by token tile: a step is one
    chunk of ``c`` pairs of one run (one expert's pairs inside one token
    tile, contiguous in ``src``); the tile of ``tt`` tokens stays in
    VMEM while its runs are added row by row, exact float32 adds in a
    fixed order (expert by expert), a tile without a pair is never
    touched, and nothing past the listed pairs is read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h = total.shape

    def at_chunk(i, tile, chunk, *_):
        return chunk[i], 0

    def at_tile(i, tile, *_):
        return tile[i], 0

    return pl.pallas_call(
        _rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(walk[0].shape[0],),
            in_specs=[pl.BlockSpec((c, h), at_chunk),
                      pl.BlockSpec((tt, h), at_tile)],
            out_specs=pl.BlockSpec((tt, h), at_tile)),
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        input_output_aliases={7: 0},
        # steps run in order: a token tile is written when it is left
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP,
                                 _rows_vmem_bytes(h, tt) + 8 * 2 ** 20)),
        name="grouped_expert_ffn_rows",
        interpret=interpret,
    )(*walk, rows, src, total)


def _held_windows(x, idx, weights, w_gate, w_up, w_down, live, tm, wt, win,
                  tt, interpret):
    """A long prefill, a trainer's forward: the held pairs
    (:func:`_held_pairs`), ``win`` of them at a time, each window's rows
    added into a float32 sum (:func:`_add_rows`)."""
    n, h = x.shape
    held = w_up.shape[0]
    weights = weights.astype(jnp.float32)
    pairs, window, runs = _held_pairs(idx, held, live, tt)
    c = min(_CHUNK, tm)

    def one(w, y):
        rows, key_w, slot = window(w, win)
        ws = jnp.where(slot, weights[rows], 0.0)
        out = _window(x, rows, key_w, ws.sum(axis=1),
                      (w_gate, w_up, w_down), tm, wt, interpret)
        return _add_rows(y, out, rows, runs(w, win, c), tt, c, interpret)

    y = lax.fori_loop(0, (pairs + win - 1) // win, one,
                      jnp.zeros((n, h), jnp.float32))
    return y.astype(x.dtype)


#: jitted, so that the layers of a program share one trace and one
#: Mosaic lowering of the kernel, as ``ops.paged_attention`` does
_resident_jit = jax.jit(
    _resident, static_argnames=("tm", "wt", "interpret"))
_held_windows_jit = jax.jit(
    _held_windows, static_argnames=("tm", "wt", "win", "tt", "interpret"))


# --- the backward ---------------------------------------------------------------
# Over the same listing of the held pairs, expert by expert, a window at a
# time, and the same visits (expert, row tile).  With ``act = silu(g) * u``,
# ``g = x Wg``, ``u = x Wu`` and a pair's result ``w * act Wd``:
#   d act = w * dY Wd^T,      d w  = <act, dY Wd^T>,
#   dX    = dG Wg^T + dU Wu^T (scatter-added to the rows' order),
#   dWd[e] = (w * act)_e^T dY_e,  dWg[e] = X_e^T dG_e,  dWu[e] = X_e^T dU_e.
# ``grouped_expert_ffn_dx`` recomputes ``g`` and ``u`` (under recomputation
# by layer they would be remade anyway) and returns the pairs' dX, d w and
# the three (pairs, width) arrays the bank's gradients are products of;
# ``grouped_expert_ffn_dw`` is one product grouped by expert, ``out[e] +=
# A_e^T B_e``, that walks an expert's row tiles and sums in float32 into a
# bank-shaped accumulator carried through the windows (an expert with no
# row keeps its zeros).

def _mine(v, tid_ref, lo_ref, hi_ref, tm):
    pair = tid_ref[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return jnp.logical_and(pair >= lo_ref[v], pair < hi_ref[v])


def _dx_kernel(eid_ref, tid_ref, lo_ref, hi_ref, total_ref,
               x_ref, dy_ref, w_ref, gate_ref, up_ref, down_ref,
               dx_ref, dw_ref, dg_ref, du_ref, act_ref):
    from jax.experimental import pallas as pl

    v = pl.program_id(0)
    tm = x_ref.shape[0]

    @pl.when(v < total_ref[0])
    def _visit():
        x, dy, w = x_ref[...], dy_ref[...], w_ref[...]
        g = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        act = silu * u
        nt = (((1,), (1,)), ((), ()))
        dact0 = lax.dot_general(dy, down_ref[0], nt,
                                preferred_element_type=jnp.float32)
        dact = dact0 * w
        dg = (dact * u * (sig + silu * (1.0 - sig))).astype(x.dtype)
        du = (dact * silu).astype(x.dtype)
        dx = lax.dot_general(dg, gate_ref[0], nt,
                             preferred_element_type=jnp.float32) \
            + lax.dot_general(du, up_ref[0], nt,
                              preferred_element_type=jnp.float32)
        mine = _mine(v, tid_ref, lo_ref, hi_ref, tm)
        # the tile's first visit owes the other experts' rows nothing yet
        opened = jnp.logical_or(
            v == 0, tid_ref[jnp.maximum(v - 1, 0)] != tid_ref[v])

        def put(ref, val):
            rest = jnp.where(opened, jnp.zeros_like(val), ref[...])
            ref[...] = jnp.where(mine, val, rest)

        put(dx_ref, dx)
        put(dw_ref, (act * dact0).sum(axis=1, keepdims=True))
        put(dg_ref, dg)
        put(du_ref, du)
        put(act_ref, (act * w).astype(x.dtype))


def _dw_kernel(eid_ref, tid_ref, lo_ref, hi_ref, total_ref,
               a_ref, b_ref, acc_ref, o_ref):
    from jax.experimental import pallas as pl

    v = pl.program_id(0)
    tm = a_ref.shape[0]
    first = jnp.logical_or(v == 0,
                           eid_ref[jnp.maximum(v - 1, 0)] != eid_ref[v])

    @pl.when(first)
    def _open():
        o_ref[...] = acc_ref[...]

    @pl.when(v < total_ref[0])
    def _visit():
        # the visit's own pairs alone, in both factors: the tile's other
        # rows are another expert's, or nobody's
        mine = _mine(v, tid_ref, lo_ref, hi_ref, tm)
        a = jnp.where(mine, a_ref[...], jnp.zeros_like(a_ref[...]))
        b = jnp.where(mine, b_ref[...], jnp.zeros_like(b_ref[...]))
        o_ref[0] += lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)


def _window_bwd(xw, dyw, key, ws, bank, accs, tm, interpret):
    """One window of the backward: the pairs' rows ``xw`` and ``dyw`` (W,
    H), experts ``key`` (W,) sorted, weights ``ws`` (W,); ``accs`` the
    three float32 bank gradients so far -> (dX (W, H) f32, d w (W,) f32,
    the three accumulators); a pair nobody computed lies in a tile that
    may never have been written: no visit reads it (``_dw_kernel``
    keeps a visit's own pairs, :func:`_add_rows` the listed ones)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w_gate, w_up, w_down = bank
    held, h, i = w_gate.shape
    win = key.shape[0]
    eid, tid, lo, hi, total = _visits(key, held, tm)
    itemsize = np.dtype(w_gate.dtype).itemsize

    def at_rows(v, eid, tid, *_):
        return tid[v], 0

    def at_expert(v, eid, *_):
        return eid[v], 0, 0

    need = 2 * 3 * h * i * itemsize + 4 * tm * h * (itemsize + 2) \
        + tm * (8 * i + 2 * h) * 4
    dx, dw, dg, du, act = pl.pallas_call(
        _dx_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(eid.shape[0],),
            in_specs=[pl.BlockSpec((tm, h), at_rows),
                      pl.BlockSpec((tm, h), at_rows),
                      pl.BlockSpec((tm, 1), at_rows),
                      pl.BlockSpec((1, h, i), at_expert),
                      pl.BlockSpec((1, h, i), at_expert),
                      pl.BlockSpec((1, i, h), at_expert)],
            out_specs=[pl.BlockSpec((tm, h), at_rows),
                       pl.BlockSpec((tm, 1), at_rows),
                       pl.BlockSpec((tm, i), at_rows),
                       pl.BlockSpec((tm, i), at_rows),
                       pl.BlockSpec((tm, i), at_rows)]),
        out_shape=[jax.ShapeDtypeStruct((win, h), jnp.float32),
                   jax.ShapeDtypeStruct((win, 1), jnp.float32),
                   jax.ShapeDtypeStruct((win, i), xw.dtype),
                   jax.ShapeDtypeStruct((win, i), xw.dtype),
                   jax.ShapeDtypeStruct((win, i), xw.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_CAP, need + 16 * 2 ** 20)),
        name="grouped_expert_ffn_dx",
        interpret=interpret,
    )(eid, tid, lo, hi, total, xw, dyw, ws[:, None], w_gate, w_up, w_down)

    def by_expert(a, b, acc):
        """``acc[e] += a_e^T b_e`` over the window's pairs."""
        ka, kb = a.shape[1], b.shape[1]
        return pl.pallas_call(
            _dw_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(eid.shape[0],),
                in_specs=[pl.BlockSpec((tm, ka), at_rows),
                          pl.BlockSpec((tm, kb), at_rows),
                          pl.BlockSpec((1, ka, kb), at_expert)],
                out_specs=pl.BlockSpec((1, ka, kb), at_expert)),
            out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=min(
                    _VMEM_CAP, 4 * ka * kb * 4 + 4 * tm * (ka + kb) * itemsize
                    + tm * ka * 4 + 16 * 2 ** 20)),
            name="grouped_expert_ffn_dw",
            interpret=interpret,
        )(eid, tid, lo, hi, total, a, b, acc)

    d_gate, d_up, d_down = accs
    accs = (by_expert(xw, dg, d_gate), by_expert(xw, du, d_up),
            by_expert(act, dyw, d_down))
    return dx, jnp.where(key < held, dw[:, 0], 0.0), accs


def _grouped_bwd(x, idx, weights, w_gate, w_up, w_down, live, dy, tm, win,
                 tt, interpret):
    """-> (dX (N, H), d weights (N, k) f32, dWg, dWu, dWd in the bank's
    dtype)."""
    n, h = x.shape
    held = w_gate.shape[0]
    weights = weights.astype(jnp.float32)
    dy = dy.astype(x.dtype)
    pairs, window, runs = _held_pairs(idx, held, live, tt)
    c = min(_CHUNK, tm)

    def one(w, carry):
        dx, dws, accs = carry
        rows, key_w, slot = window(w, win)
        ws = jnp.where(slot, weights[rows], 0.0).sum(axis=1)
        dxw, dwp, accs = _window_bwd(
            x[rows], dy[rows], key_w, ws, (w_gate, w_up, w_down), accs, tm,
            interpret)
        dx = _add_rows(dx, dxw, rows, runs(w, win, c), tt, c, interpret)
        # rows of k values: XLA's scatter-add of them is under 0.05 ms a
        # window of 16,384 pairs (PERF.md, PR 46)
        dws = dws.at[rows].add(jnp.where(slot, dwp[:, None], 0.0))
        return dx, dws, accs

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
    dx, dws, accs = lax.fori_loop(
        0, (pairs + win - 1) // win, one,
        (jnp.zeros((n, h), jnp.float32), jnp.zeros(weights.shape, jnp.float32),
         (zeros(w_gate), zeros(w_up), zeros(w_down))))
    return (dx.astype(x.dtype), dws,
            *(a.astype(w_gate.dtype) for a in accs))


_grouped_bwd_jit = jax.jit(_grouped_bwd,
                           static_argnames=("tm", "win", "tt", "interpret"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _grouped(x, idx, weights, w_gate, w_up, w_down, live, static):
    tm, wt, win, tt, interpret, _kind = static
    form = rows_form(*idx.shape, x.shape[1], w_up.shape[2],
                     np.dtype(w_up.dtype).itemsize, (tm, wt), win)
    telemetry.gauge("grouped_ffn.fwd.window_pairs", win)
    # where the rows cross between the tokens' order and the experts':
    # in VMEM inside the one kernel, or window by window in
    # ``grouped_expert_ffn_rows``
    telemetry.gauge("grouped_ffn.rows_form", form)
    if form == "resident":
        return _resident_jit(x, idx, weights, w_gate, w_up, w_down, live,
                             tm=tm, wt=wt, interpret=interpret)
    telemetry.gauge("grouped_ffn.token_tile", tt)
    return _held_windows_jit(x, idx, weights, w_gate, w_up, w_down, live,
                             tm=tm, wt=wt, win=win, tt=tt,
                             interpret=interpret)


def _grouped_fwd(x, idx, weights, w_gate, w_up, w_down, live, static):
    return (_grouped(x, idx, weights, w_gate, w_up, w_down, live, static),
            (x, idx, weights, w_gate, w_up, w_down, live))


def _grouped_vjp(static, res, dy):
    x, idx, weights, w_gate, w_up, w_down, live = res
    tm, wt, win, tt, interpret, kind = static
    if kind != "swiglu":
        raise MXNetError(
            f"grouped_expert_ffn's backward is written for \"swiglu\" "
            f"experts; a bank of kind {kind!r} has none yet")
    if wt != w_gate.shape[2]:
        raise NotImplementedError(
            "grouped_expert_ffn's backward takes whole experts a visit; an "
            f"expert walked in width tiles of {wt} has none yet")
    # the backward's windows are the forward's; a call whose pairs all
    # fit one window forward lists its held pairs alone, in that window
    telemetry.gauge("grouped_ffn.bwd.row_tile", tm)
    telemetry.gauge("grouped_ffn.bwd.window_pairs", win)
    telemetry.gauge("grouped_ffn.token_tile", tt)
    dx, dws, d_gate, d_up, d_down = _grouped_bwd_jit(
        x, idx, weights, w_gate, w_up, w_down, live, dy, tm=tm, win=win,
        tt=tt, interpret=interpret)
    return (dx, None, dws.astype(weights.dtype), d_gate, d_up, d_down, None)


_grouped.defvjp(_grouped_fwd, _grouped_vjp)


def grouped_expert_ffn(x, idx, weights, w_gate, w_up, w_down, live=None,
                       row_tile=None, width_tile=None, window=None,
                       token_tile=None, interpret=False, kind="swiglu"):
    """``x`` (N, H) in the bank's dtype; ``idx`` (N, k) int32 the
    experts of each row COUNTED FROM THE BANK'S FIRST (an id outside
    ``[0, held)`` is another chip's expert: left out); ``weights``
    (N, k) float32; the bank ``w_gate`` / ``w_up`` (held, H, I),
    ``w_down`` (held, I, H); ``live`` (N,) bool the rows a request owns
    (default: all; the others are left out like another chip's pairs
    and come back zero).  -> (N, H) in ``x``'s dtype: the sum over each
    row's held experts of weight x SwiGLU, or with ``kind`` (static)
    ``"relu2"`` of weight x ``relu(x w_up)^2 w_down``: two matrices an
    expert, ``w_gate`` None.  ``row_tile``,
    ``width_tile``, ``window`` (sorted pairs, whole row tiles) and
    ``token_tile`` (rows of the float32 sum a step of
    ``grouped_expert_ffn_rows`` keeps) default to what the shapes say
    (:func:`tiles`, :func:`window_pairs`, :func:`token_rows`); tests
    and ``tools/routed_ffn_bench.py`` set them.

    Differentiable (``jax.custom_vjp``) in ``x``, ``weights`` and the
    three banks: the backward runs over the same held pairs and the same
    tiles (``grouped_expert_ffn_dx``, ``grouped_expert_ffn_dw``), dropless
    at any skew, an expert with no row getting zeros; a ``"relu2"``
    bank has no backward yet and says so when differentiated."""
    check_kind(kind, w_gate)
    h, i = w_up.shape[1:]
    tm, wt = tiles(h, i, np.dtype(w_up.dtype).itemsize) or (ROW_TILE, i)
    tm, wt = row_tile or tm, width_tile or wt
    win = window or window_pairs(*idx.shape, h, tm)
    tt = token_tile or token_rows(idx.shape[0], h)
    return _grouped(x, idx, weights, w_gate, w_up, w_down, live,
                    (tm, wt, win, tt, bool(interpret), kind))

"""Grouped expert feed-forward: one Pallas TPU kernel that streams each
routed expert's weights once and computes only the rows routed to it.

Reference: NONE (the reference has no sparse experts).
``models.moe.routed_ffn``'s other form runs every held expert on every
row under a combine matrix; past some 240 rows a call that product is
bound by operations nobody asked for (PERF.md, PR 31).  Here:

- the ``N x k`` (row, expert) pairs are sorted by expert in XLA; pair
  ``p`` of the sorted list is a row of ``xs`` (the token's hidden
  state, gathered) and a float32 weight.  Groups are NOT padded: a row
  tile of ``ROW_TILE`` sorted pairs that straddles a group boundary is
  visited once for each expert it holds rows of, and a visit keeps the
  rows of its own expert (``lo <= pair < hi``);
- a grid step is one visit ``(expert, row tile)``, listed expert by
  expert (:func:`_visits`) and prefetched as scalars, so the block
  index of an expert's ``w_gate`` / ``w_up`` ``(H, I)`` and ``w_down``
  ``(I, H)`` changes only when the expert does: the pipeline fetches
  each touched expert once, straight from the stacked bank as it lies
  in HBM, and an expert without a row never.  Steps past the last
  visit repeat its indices and compute nothing;
- gate, up, ``silu(gate) * up`` and down run inside the visit: the
  ``(rows, I)`` intermediate never leaves VMEM.  Operands in the
  bank's dtype, float32 accumulation in each product, the pair's
  weight applied in float32 to the float32 down product;
- the kernel's output is a float32 row a pair; XLA puts the pairs back
  in their rows' order and sums a row's ``k`` in float32, one cast at
  the end.

An MXU pass costs the same for 8 rows as for 128, so the row tile is
128: fewer visits, each at the price of one.  Tiles of 64 and 256 read
within 2% of it at every size from 128 to 2,048 rows (PERF.md, PR 31):
the call is bound by the experts' bytes, not by its tiles.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

#: reviewed signature budget (mxlint T15): inlined into the step or
#: prefill program that calls it; alone (tests_tpu/, tools/) one
#: program per operand shapes
__compile_signatures__ = {
    "grouped_expert_ffn":
        "0 inside a serving program; 1 per (operand shapes, row_tile) "
        "when called alone",
}

#: sorted pairs a visit computes (see the module text)
ROW_TILE = 128

#: fewest rows a call at which ``routed_ffn`` takes this form.  Every
#: expert on every row costs ``max(N x bank operations / peak, bank
#: bytes / bandwidth)`` and turns compute-bound at 2 x 197e12 / 819e9 =
#: 240 rows whatever the bank's shape; this form costs the touched
#: experts' bytes and some 0.15 us a pair of sorting and gathering.
#: On the v5e (PERF.md, PR 31: one layer with its routing, ms a call,
#: every expert / this form; 128 experts of 768, 8 a row): 1.73 / 1.85
#: at 128 rows, 1.86 / 1.99 at 256, 3.42 / 2.20 at 512, 6.74 / 2.75 at
#: 1,024, 13.76 / 4.30 at 2,048; 64 experts of 1,536, 4 a row: 1.78 /
#: 1.82 at 128, 1.87 / 1.91 at 256, 3.41 / 2.10 at 512, 13.56 / 3.07 at
#: 2,048.  The lines cross near 290 rows; the constant lies between the
#: measured sides, 256 and 512.
GROUPED_MIN_ROWS = 384

#: VMEM a call may ask for: a v5e core has 128 MiB
_VMEM_CAP = 100 * 2 ** 20


def _vmem_bytes(hidden, width, itemsize, tm):
    """What a call keeps in VMEM: two experts (the one computed, the
    one in flight), two tiles each of rows, weights and output, and the
    visit's float32 intermediates."""
    expert = 3 * hidden * width * itemsize
    tiles = 2 * tm * (hidden * itemsize + 128 * 4 + hidden * 4)
    temps = tm * (3 * width + 2 * hidden) * 4
    return 2 * expert + tiles + temps


def applicable(platform, mesh, rows, k, held, hidden, width, itemsize=2):
    """Whether ``routed_ffn`` evaluates ``rows`` rows of ``k`` experts
    each over a bank of ``held`` experts ``(hidden, width)`` through
    this kernel, from what the caller observes: the platform, the mesh
    (a sharded bank would need a ``shard_map`` wrapper: it keeps every
    expert on every row) and static shapes: lanes of 128, two experts
    in VMEM, and enough rows a call that the other form is bound by its
    operations and not by the bank's bytes."""
    return (platform == "tpu" and mesh is None
            and rows >= GROUPED_MIN_ROWS and 1 <= k <= held
            and hidden % 128 == 0 and width % 128 == 0
            and _vmem_bytes(hidden, width, itemsize, ROW_TILE) <= _VMEM_CAP)


def _visits(key, held, tm):
    """The kernel's walk over sorted expert ids ``key`` (Mp,), ``held``
    for a pair no held expert computes: visit ``v`` is expert ``eid[v]``
    on row tile ``tid[v]``, whose own pairs are ``lo[v] <= p < hi[v]``;
    ``total`` visits, listed expert by expert and so tile by tile too,
    the static rest repeating the last one."""
    mp = key.shape[0]
    ends = jnp.searchsorted(key, jnp.arange(held, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    sizes = ends - starts
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    vend = jnp.cumsum(tiles)
    total = vend[-1]
    # a tile boundary or a group boundary starts every visit but one
    v = jnp.arange(mp // tm + held - 1, dtype=jnp.int32)
    v = jnp.minimum(v, jnp.maximum(total - 1, 0))
    eid = jnp.minimum(jnp.searchsorted(vend, v, side="right"),
                      held - 1).astype(jnp.int32)
    tid = starts[eid] // tm + (v - (vend - tiles)[eid])
    return eid, tid.astype(jnp.int32), starts[eid], ends[eid], total[None]


def _kernel(eid_ref, tid_ref, lo_ref, hi_ref, total_ref,
            x_ref, w_ref, gate_ref, up_ref, down_ref, o_ref):
    from jax.experimental import pallas as pl

    v = pl.program_id(0)
    tm = x_ref.shape[0]

    @pl.when(v < total_ref[0])
    def _visit():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        act = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        y = jnp.dot(act, down_ref[0], preferred_element_type=jnp.float32)
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


def _grouped_expert_ffn(x, idx, weights, w_gate, w_up, w_down,
                        row_tile=ROW_TILE, interpret=False):
    """``x`` (N, H) in the bank's dtype; ``idx`` (N, k) int32 the
    experts of each row COUNTED FROM THE BANK'S FIRST (an id outside
    ``[0, held)`` is another chip's expert: left out); ``weights``
    (N, k) float32; the bank ``w_gate`` / ``w_up`` (held, H, I),
    ``w_down`` (held, I, H).  -> (N, H) in ``x``'s dtype: the sum over
    each row's held experts of weight x SwiGLU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h = x.shape
    k = idx.shape[1]
    held, _, i = w_gate.shape
    tm = int(row_tile)
    m = n * k
    mp = -(-m // tm) * tm
    there = jnp.logical_and(idx >= 0, idx < held)
    key = jnp.where(there, idx, held).reshape(-1).astype(jnp.int32)
    key = jnp.pad(key, (0, mp - m), constant_values=held)
    key, order = lax.sort((key, jnp.arange(mp, dtype=jnp.int32)),
                          num_keys=1)
    eid, tid, lo, hi, total = _visits(key, held, tm)
    xs = x[jnp.minimum(order // k, n - 1)]
    ws = jnp.pad(weights.reshape(-1).astype(jnp.float32),
                 (0, mp - m))[order][:, None]

    rows = lambda v, eid, tid, *_: (tid[v], 0)          # noqa: E731
    bank = lambda v, eid, *_: (eid[v], 0, 0)            # noqa: E731
    need = _vmem_bytes(h, i, np.dtype(w_gate.dtype).itemsize, tm)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(eid.shape[0],),
            in_specs=[pl.BlockSpec((tm, h), rows),
                      pl.BlockSpec((tm, 1), rows),
                      pl.BlockSpec((1, h, i), bank),
                      pl.BlockSpec((1, h, i), bank),
                      pl.BlockSpec((1, i, h), bank)],
            out_specs=pl.BlockSpec((tm, h), rows)),
        out_shape=jax.ShapeDtypeStruct((mp, h), jnp.float32),
        # visits run in order: an expert's weights stay while it is
        # the next visit's too, a row tile's output until it is left
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(_VMEM_CAP, need + 8 * 2 ** 20))),
        name="grouped_expert_ffn",
        interpret=interpret,
    )(eid, tid, lo, hi, total, xs, ws, w_gate, w_up, w_down)
    # back to the rows' order; a pair nobody computed lies in a tile
    # that may never have been written
    back = jnp.zeros((mp,), jnp.int32).at[order].set(
        jnp.arange(mp, dtype=jnp.int32), unique_indices=True)[:m]
    y = jnp.where(there[:, :, None], out[back].reshape(n, k, h), 0.0)
    return y.sum(axis=1).astype(x.dtype)


#: jitted, so that the layers of a program share one trace and one
#: Mosaic lowering of the kernel, as ``ops.paged_attention`` does
grouped_expert_ffn = jax.jit(_grouped_expert_ffn,
                             static_argnames=("row_tile", "interpret"))

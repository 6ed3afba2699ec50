"""Flash attention: fused online-softmax attention as a Pallas TPU kernel.

Reference: the reference has no flash attention — its closest analog is the
contrib interleaved self-attention matmuls (``src/operator/contrib/
transformer.cc:?``, SURVEY §2.2 contrib row) which materialise the full
(T, T) score matrix in HBM.  This kernel is the TPU-native replacement:
scores live in VMEM one (block_q × block_k) tile at a time, the online
softmax keeps running (m, l) statistics, and the MXU sees two back-to-back
matmuls per tile.  HBM traffic drops from O(T²) to O(T·D).  One forward
body serves the trainer (``flash_attention_raw``: as many KV heads as
query heads, the log-sum-exp saved for the backward; ``v`` may have a
width of its own, latent attention's expanded heads being 192 wide for q
and k and 128 for v, and ``o``, ``do`` and ``dv`` then have v's) and the
served decoders' prefill
(``prefill_flash_attention``: the query heads of a KV head in one tile,
each prompt's true length bounding the tiles computed).

A grid step.  The grid is ``(B * Hkv / hb, nq, nk)`` (``dkv``: ``nk``
before ``nq``): a step is one (q tile, k tile) pair of ``hb`` (batch,
head) rows.  Under the causal mask past one tile the trainer's three
kernels walk ``(B * H, live steps)`` instead, the pairs under the
diagonal as a scalar-prefetched list (``_live_steps``): no step is
spent above it.  ``hb`` is 1 — a step is a tile pair of one head of one
batch row — for the served prefill and wherever a sequence spans more
than one tile.  Where one tile holds a head's whole sequence (the
trainer at sequences up to 512: BERT's 128) a step of one row is all
fixed cost, so it takes ``train_tiles`` rows at once, a leading batch
axis of the same arithmetic; past one tile each of the trainer's three
kernels has tiles of its own (``train_blocks``).  Both choices are
static, from shapes, and the gauges ``flash.rows_per_step.fwd`` /
``.dq`` / ``.dkv``, ``flash.grid_steps.*`` and ``flash.live_steps.*``
record them where the program is traced.  The backward's row statistics
(``lse``, ``delta``) travel along lanes, ``(bh, 1, T)``, not one number
a lane tile, and so does the forward's ``lse`` where a step is several
rows.

Two entries for the trainer.  ``flash_attention_raw`` is head-major,
``(B, H, T, D)``: the grid above, for callers that hold their heads that
way (``models/llama.py``, ``models/joyai_flash.py``, ``parallel/ring.py``).
``flash_attention_tokens`` is token-major, ``(B, T, N x H)`` as a
model's projections leave q, k and v and as its output projection wants
o: grid ``(B / bb, N x H / 128)``, a block ``(bb, T, 128)`` of ``bb``
batch rows (``tokens_rows``) by the whole sequence by one lane tile,
which is two heads of 64 (each reached by a lane mask: a product that
contracts the tile's 128 lanes with the other head's zeroed) or one head
a multiple of 128 wide.  Nothing is transposed in HBM around it, forward
or backward; ``lse`` is ``(B, N / 2, 2, T)``, a row a (batch, head);
``delta`` is taken inside ``dq`` and ``dkv``, which read ``o``.
``ops.attention.sdpa_raw`` takes it where ``tokens_applicable`` reads it
from the operands' shapes (one tile of sequence, one chip); the gauge
``flash.token_major.*`` and ``train_form(..., layout="tokens")`` say
which entry a program took.

Backward: ``jax.custom_vjp``; on the TPU the two Pallas kernels below
(``dq``, ``dkv``), elsewhere a K-block-chunked jnp backward
(``lax.scan``) — recompute-based, so backward memory is O(T·block) too.
Non-TPU platforms (the CPU test mesh) fall back to a jnp online-softmax
scan with identical semantics AND the same O(T*block) score memory, so
CPU lowerings (virtual-mesh scale proofs) price the flash memory
profile rather than a dense (T, T) materialization.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

#: reviewed signature budget (mxlint T15): the jit of the served
#: prefill's entry is inlined into the prefill program that calls it and
#: compiles nothing of its own there; called alone (tests_tpu/, tools/)
#: it is one program per operand shapes
__compile_signatures__ = {
    "prefill_flash_attention":
        "0 inside a serving program; 1 per operand shapes when called "
        "alone",
}


def _on_tpu():
    import os

    if os.environ.get("MXT_FORCE_PALLAS_FLASH") == "1":
        # offline AOT topology compiles (tools/_tpu_topology.py): the
        # PROCESS backend is cpu but the jit target is a real TPU
        # topology client, so the mosaic kernel is both valid and the
        # true memory profile — the caller vouches for the target
        return True
    # in a mixed-platform process, route by where the dispatch's operands
    # actually live (r5 on-chip parity finding: the cpu-oracle leg was
    # handed a mosaic kernel); the hint is published by apply_op and
    # CachedOp dispatch whenever their operands are concrete
    from .registry import current_dispatch_platform

    hint = current_dispatch_platform()
    if hint is not None:
        return hint == "tpu"
    return jax.devices()[0].platform == "tpu"


# --- jnp reference (fallback + backward building block) ---------------------

def _sdpa_ref(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _fa_forward_chunked(q, k, v, causal, scale, block=512):
    """jnp online-softmax forward scanned over K blocks — the non-TPU
    analog of the pallas kernel with the SAME O(T*block) score memory.
    Fully-masked query rows (causal with tq > tk) output ZEROS — the
    flash-kernel convention, unlike the dense softmax's NaN; pinned by
    tests/test_llama.py::test_flash_attention_degenerate_fully_masked_rows.
    Replaces the dense ``_sdpa_ref`` fallback on CPU lowerings so the
    scale-proof memory analysis (tools/scale_proof.py) prices the
    flash memory profile, not a (T, T) materialization the real TPU
    program never allocates."""
    tq, tk = q.shape[-2], k.shape[-2]
    block = min(block, tk)
    # pad K/V up to a block multiple and mask the tail: non-multiple
    # (even prime) lengths keep the O(T*block) profile AND the block-
    # sized matmuls — neither a dense (tq, tk) slab nor a length-tk
    # scan of width-1 steps
    pad = (-tk) % block
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if pad:
        widths = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        kf = jnp.pad(kf, widths)
        vf = jnp.pad(vf, widths)
    nk = (tk + pad) // block
    qf = q.astype(jnp.float32)
    kb = jnp.moveaxis(kf.reshape(*kf.shape[:-2], nk, block,
                                 kf.shape[-1]), -3, 0)
    vb = jnp.moveaxis(vf.reshape(*vf.shape[:-2], nk, block,
                                 vf.shape[-1]), -3, 0)
    qpos = jnp.arange(tq)

    # carry init DERIVED from q (x*0 instead of fresh zeros/full): under
    # shard_map the varying-axes checker requires the scan carry to
    # inherit the operands' manual axes — fresh literals are unvarying
    # and fail the carry typematch (jax shard-map vma rules)
    m0 = qf[..., 0] * 0 - jnp.inf
    l0 = qf[..., 0] * 0
    acc0 = qf * 0 if vf.shape[-1] == qf.shape[-1] else \
        qf[..., :1] * 0 + jnp.zeros((vf.shape[-1],), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        s = jnp.einsum("...qd,...kd->...qk", qf, kj,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * block + jnp.arange(block)
        keep = kpos[None, :] < tk  # padded tail keys never attend
        if causal:
            keep = keep & (qpos[:, None] + (tk - tq) >= kpos[None, :])
        s = jnp.where(keep, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p, vj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), ()

    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), (jnp.arange(nk), kb, vb))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# --- pallas forward kernel ---------------------------------------------------

def _tile_runs(qi, kj, n, *, block_q, block_k, causal):
    """Whether the (qi, kj) tile holds anything a row needs: not wholly
    above the causal diagonal and, where the batch row's true length
    ``n`` is known (prefill: rows and keys past it are padding that no
    real row reads), not wholly past it.  The kernel's predicate and the
    K/V index map's: a tile that does not run is not fetched."""
    run = kj >= 0
    if causal:
        run = (qi + 1) * block_q > kj * block_k
    if n is not None:
        run = run & (kj * block_k < n) & (qi * block_q < n)
    return run


def _live_steps(nq, nk, block_q, block_k, q_outside=True):
    """The (q tile, k tile) pairs of a causal grid that ``_tile_runs``, in
    the order a kernel sweeps them (q tiles outside and k tiles inside,
    or the other way for ``dkv``), as two tuples: outer tiles, inner
    tiles.  A kernel whose grid is this list (scalar-prefetched) has no
    step above the diagonal: on the v5e such a step, which computes and
    fetches nothing, still cost about a microsecond (PERF.md, PR 45)."""
    outer, inner = (nq, nk) if q_outside else (nk, nq)
    pairs = [(a, b) for a in range(outer) for b in range(inner)
             if _tile_runs(*((a, b) if q_outside else (b, a)), None,
                           block_q=block_q, block_k=block_k, causal=True)]
    return tuple(zip(*pairs))


def _dot(a, b, ca, cb):
    """``a`` . ``b`` over axis ``ca`` of ``a``'s matrix and ``cb`` of
    ``b``'s (0 or 1, counted within the last two axes), float32
    results; a leading axis, where the operands have one, is batched: a
    step's ``hb`` rows."""
    n = a.ndim - 2
    batch = tuple(range(n))
    return lax.dot_general(a, b, (((n + ca,), (n + cb,)), (batch, batch)),
                           preferred_element_type=jnp.float32)


def _fa_kernel(*refs, block_q, block_k, causal, scale, nk, kv_heads,
               with_lse, bounded, span=1, hb=1, listed=False):
    """Canonical 3-D-grid flash kernel: grid (B * Hkv / hb, nq, nk), kv
    innermost; running (m, l, acc) live in VMEM scratch across the kv
    sweep so pallas double-buffers the K/V block loads.

    A grid step is one (q tile, k tile) pair of ``hb`` rows of
    ``B * Hkv``: one row everywhere but where ``train_tiles`` says
    otherwise (a head's whole sequence in one tile); the ``hb`` rows are
    then a leading batch axis of every array in the body, each row's
    arithmetic what it is alone.

    A grid row is one KV head and the ``G = H / Hkv`` query heads it
    serves (G = 1 without GQA): their ``block_q`` rows each are laid as
    one ``(G * block_q, d)`` tile against each K tile, so K and V are
    fetched once a group and never repeated.  The products take the
    operands in their stored dtype (bf16: one MXU pass) with float32
    results; the running max, sum and accumulator are float32 and the
    probabilities are cast to V's dtype for the second product — the
    arithmetic of ``ops.attention.masked_attention``.

    ``bounded``: the first ref is a scalar-prefetched ``(B,)`` of true
    lengths, and tiles wholly past a row's length are skipped (a q tile
    of padding alone yields zeros).  The mask is written on positions
    (``qpos >= kpos``), so a query offset behind a reused prefix is one
    more scalar added to ``qpos``, not another kernel.

    ``span`` (static; divides both tiles): a block decoder's block
    length.  A row then sees its whole block of ``span`` positions and
    every block before it, ``kpos < (qpos // span + 1) * span``; the
    blocks end where the tiles do, so the same tiles run and the same
    ones pay for the mask.

    ``listed``: the grid is ``(B * H, live steps)`` and the first two
    refs are ``_live_steps``' scalar-prefetched lists, a step's q tile
    and its k tile (the trainer under the causal mask past one tile;
    never with ``bounded``)."""
    from jax.experimental import pallas as pl

    len_ref = refs[0] if bounded else None
    first = 2 if listed else int(bounded)     # the scalar-prefetched refs
    q_ref, k_ref, v_ref, o_ref = refs[first:first + 4]
    lse_ref = refs[first + 4] if with_lse else None
    m_ref, l_ref, acc_ref = refs[-3:]
    group, _, d = q_ref.shape[1:]
    dv = v_ref.shape[-1]          # v's own width: o's and the accumulator's
    rows = group * block_q
    # the step's rows of ``bh``: one (the program every caller had), or
    # ``hb`` of them as a leading batch axis of every array below
    lead, blk = ((), 0) if hb == 1 else ((hb,), slice(None))
    if listed:
        # grid (bh, live steps), the trainer's causal grid past one tile
        # (``_live_steps``): the step's tiles are read from the two lists
        qi, kj = (ref[pl.program_id(1)] for ref in refs[:2])
        last = jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)
    else:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        last = nk - 1
    n = len_ref[pl.program_id(0) // kv_heads] if bounded else None

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(masked):
        q = q_ref[blk].reshape(*lead, rows, d)
        v = v_ref[blk]
        s = _dot(q, k_ref[blk], 1, 1) * scale
        if masked:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (group, block_q, block_k), 1).reshape(
                    rows, block_k)
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (rows, block_k), 1)
            if span > 1:
                qpos = qpos // span * span + (span - 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        # tq == tk and the kj == 0 tile runs first, so every row has met
        # key 0 and its running max is finite from its first tile on
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(
            p.astype(v.dtype), v, 1, 0)
        m_ref[...] = m_new

    run = _tile_runs(qi, kj, n, block_q=block_q, block_k=block_k,
                     causal=causal)
    if causal:
        # only a tile the diagonal crosses pays for the mask
        crossed = kj * block_k + block_k - 1 > qi * block_q
        pl.when(run & crossed)(lambda: tile(True))
        pl.when(run & jnp.logical_not(crossed))(lambda: tile(False))
    else:
        pl.when(run)(lambda: tile(False))

    @pl.when(kj == last)
    def _finish():
        l = l_ref[...]
        o_ref[blk] = (acc_ref[...] / jnp.maximum(l, 1e-30)).reshape(
            *lead, group, block_q, dv).astype(o_ref.dtype)
        if with_lse:
            # log-sum-exp per query row, saved for the pallas backward;
            # a row no tile ran for keeps -inf (its backward p is
            # zeroed).  A step of one row stores it (group, block_q, 1),
            # as it is computed; a step of several rows along lanes,
            # (hb, group, block_q): see ``_fa_forward_pallas``
            lse_ref[blk] = jnp.where(
                l > 0.0, m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)),
                -jnp.inf).reshape(*lead, *lse_ref.shape[1:])


def _divisor_block(t, pref):
    for cand in (pref, 512, 256, 128):
        if cand <= t and t % cand == 0:
            return cand
    return t


#: what a step's rows may ask of the 16 MiB of VMEM a kernel gets by
#: default: three quarters.  At BERT's shape 24 rows (15.7 MB by
#: ``train_row_bytes``) compile alone and are refused inside the whole
#: vjp's program (16.56 MB); a raised ``vmem_limit_bytes`` let 32-64 rows
#: compile and made ``dq`` and ``dkv`` a quarter slower at every ``hb``
#: (PERF.md, PR 36)
TRAIN_VMEM_BYTES = 12 << 20


def train_row_bytes(tq, tk, d, itemsize=2, kernel=None, dv=None):
    """VMEM a (batch, head) row of a step of ``tq`` queries by ``tk`` keys
    asks for.

    A one-tile step (``kernel`` None; ``d`` the wider of q/k's and v's),
    by the largest of the three kernels (``dkv``): its four operand and
    two result blocks double-buffered, heads narrower than a tile's 128
    lanes padded to them, and four score-shaped float32 arrays (the
    float32 casts and the accumulators reuse what is dead).  0.655 MB at
    T = 128, D = 64 in bf16; the compiler counts 0.636 (20.35 MB at 32
    rows).

    A step of a grid of several tiles, by ``kernel`` (``"fwd"``,
    ``"dq"``, ``"dkv"``) at q and k ``d`` wide and v ``dv``: its blocks
    double-buffered, its float32 accumulators (the forward's ``m``,
    ``l`` and ``lse`` one number a lane tile), the float32 casts of a
    backward kernel's operands, and ONE score-shaped float32 array: with
    no guard on ``lse`` Mosaic walks the elementwise chain between the
    products in strips.  Against the smallest ``vmem_limit_bytes`` that
    compiles for the v5e (tiles of 512 and 1,024, heads of 64, 128,
    192 / 128 and 256 in bf16, 128 in float32; PERF.md, PR 45) this count
    lies between 2.7 MiB above and 2.3 MiB below: ``dq`` at 1,024 x
    1,024 is 12.0 MiB here and 11.8 there at heads of 192 / 128, 14.0
    and 16.3 at heads of 256, which the 16 MiB a kernel gets refuse."""
    lanes = -(-d // 128) * 128
    if kernel is None:
        return 2 * (4 * tq + 2 * tk) * lanes * itemsize + 4 * tq * tk * 4
    row = lanes + -(-(dv or d) // 128) * 128      # q or k beside do or v
    if kernel == "fwd":
        blocks, acc, casts = (tq + tk) * row, tq * (row - lanes + 512), 0
    elif kernel == "dq":
        blocks, acc, casts = tq * (row + lanes) + tk * row, tq * lanes, 1
    else:
        blocks, acc, casts = (tq + 2 * tk) * row, tk * row, 1
    if itemsize >= 4:
        casts = 0
    return (2 * blocks * itemsize + 4 * acc + 4 * casts * (tq + tk) * row
            + 4 * tq * tk)


def train_tiles(bh, tq, tk, d, itemsize=2):
    """Rows of ``bh = B * H`` a grid step of the three training kernels
    takes: 1 unless one tile holds a head's whole sequence (``nq == nk ==
    1`` at blocks of 512: sequences up to 512, where ``train_blocks``
    gives one tile); then the largest divisor of ``bh`` whose working set
    fits ``TRAIN_VMEM_BYTES``.

    On the v5e at BERT-base's ``(128 x 12, 128, 128, 64)`` in bf16, ms a
    layer-call, forward / dq / dkv (PERF.md, PR 36; the program before
    the rule is the first line): 1 row a step 1.07 / 1.13 / 1.43; with
    the statistics still one number a lane tile 4 rows 0.58 / 0.72 /
    0.90 and 16 rows 0.48 / 0.70 / 0.85; with them along lanes 4 rows
    0.57 / 0.50 / 0.60, 8 rows 0.60 / 0.46 / 0.60, 12 rows 0.53 / 0.44 /
    0.54, **16 rows 0.52 / 0.43 / 0.53**, 24 rows 0.53 / 0.42 / 0.52.
    A step of one row cost 0.70-0.93 us whatever it computed; at 16 rows
    the forward is bound by its elementwise work on the scores (0.33 us
    a row) and the backward by its float32-operand products.  A loop
    over the rows inside the step gained nothing in the forward (0.99:
    a row's two dependent products are latency that only independent
    rows in one block hide), so the rows are a batch axis."""
    one_tile = (_divisor_block(tq, min(512, tq)) == tq
                and _divisor_block(tk, min(512, tk)) == tk)
    cap = TRAIN_VMEM_BYTES // train_row_bytes(tq, tk, d, itemsize) \
        if one_tile else 1
    return max(c for c in range(1, max(1, min(cap, bh)) + 1) if bh % c == 0)


def train_blocks(kernel, tq, tk, d, dv=None, itemsize=2, causal=False):
    """(block_q, block_k) of training kernel ``kernel`` (``"fwd"``,
    ``"dq"``, ``"dkv"``) at q and k ``d`` wide and v ``dv``: one tile
    where 512 positions hold the sequence (``train_tiles``' case, rows a
    step); past it tiles of 1,024 x 1,024 where they divide the sequence
    and ``train_row_bytes`` fits them into ``TRAIN_VMEM_BYTES``, which
    the backward kernels under the causal mask take only where four of
    them span the sequence; 512 x 512 otherwise.

    On the v5e at ``(4, 32, T, 192 / 128)`` causal in bf16, ms a
    layer-call, forward / dq / dkv (PERF.md, PR 45; the program before
    the rule read 1.30 / 1.30 / 1.47 at T = 1,024, 3.83 / 4.21 / 4.92 at
    2,048 and 12.46 / 14.15 / 17.44 at 4,096, all at 512 x 512):

    ==============  ==================  ==================  =====================
    tiles           T = 1,024           2,048               4,096
    ==============  ==================  ==================  =====================
    256 x 1,024     1.23 / 1.28 / 1.47  3.55 / 3.46 / 4.06  10.73 / 11.14 / 12.99
    512 x 512       1.21 / 1.01 / 1.17  3.71 / 2.93 / 3.46  12.01 / 10.05 / 11.80
    512 x 1,024     1.10 / 1.19 / 1.39  3.06 / 3.23 / 3.81   9.15 / 10.27 / 12.09
    1,024 x 512     1.55 / 1.18 / 1.40  4.44 / 3.21 / 3.83  13.77 / 10.25 / 12.15
    1,024 x 1,024   0.88 / 1.11 / 1.33  2.91 / 3.11 / 3.71   8.63 /  9.83 / 11.65
    512 x 2,048                         3.71 / 4.05 / -     10.41 / 11.71 / -
    2,048 x 512                         5.42 / - / 4.79     15.49 / - / 13.89
    ==============  ==================  ==================  =====================

    (- : refused, more VMEM than a kernel gets.)  Made again by
    ``chiprun -- python tools/prefill_flash_bench.py --train
    4,32,4096,192,128 --causal --tiles
    256:1024,512:512,512:1024,1024:512,1024:1024,512:2048,2048:512``.
    The forward is its cost a score row a step (3.8 ns whatever the keys'
    width: ``prefill_tiles``) and wants few, wide steps.  The backward
    kernels are bound by their products (five with the scores recomputed,
    float32 operands), so a tile on the diagonal, half of it masked, is
    work lost: a third of the work at T = 1,024 in one tile of 1,024,
    where three of 512 lose a sixth; from four tiles of 1,024 a side the
    wide ones win (``dq`` by 2.2%; ``dkv`` by 1.3%, but its VMEM count is
    13.0 MiB at these heads and it stays at 512 x 512; at heads of 128 it
    goes wide: 8.27 -> 7.80).  A raised ``vmem_limit_bytes`` changed no
    time at 1,024 x 1,024 (32 and 64 MiB, on the grid before the list:
    8.92 / 10.64 / 11.90 against 8.91 / 10.65 / 11.90) and the tiles it
    admits are slower: 1,024 x 2,048 10.42 / 11.96 / 13.74, 2,048 x 1,024
    10.07 / 11.91 / 13.74, 2,048 x 2,048 10.27 / 11.82 / 13.63.  Without
    the mask 1,024 x 1,024 wins at every length (T = 4,096: 12.36 / 15.06
    / 17.96 against 18.99 / 16.47 / 19.40 at 512 x 512; 1,024: 0.99 /
    1.12 / 1.32 against 1.45 / 1.21 / 1.42); at heads of 128 causal at
    4,096 it reads 5.39 / 5.89 / 7.80 against 8.82 / 6.40 / 8.27."""
    default = (_divisor_block(tq, min(512, tq)),
               _divisor_block(tk, min(512, tk)))
    # mxlint: allow=T2 (lengths and widths are static shapes, here and below)
    if default == (tq, tk) or (
            kernel != "fwd" and causal and min(tq, tk) < 4 * 1024):
        return default
    # the forward's second best keeps the keys wide (the table's 512 x
    # 1,024); the backward kernels gain nothing from tiles that are not
    # square
    wide = ((1024, 1024), (512, 1024)) if kernel == "fwd" \
        else ((1024, 1024),)
    for bq, bk in wide:
        # mxlint: allow=T2
        if tq % bq == 0 and tk % bk == 0 and train_row_bytes(
                bq, bk, d, itemsize, kernel, dv) <= TRAIN_VMEM_BYTES:
            return bq, bk
    return default


def _say_grid(kernel, hb, steps, token_major=False):
    """The gauges of a training kernel's grid where its program is
    traced: the (batch, head) rows a step takes and the steps of a head's
    grid, all of which compute (``flash.live_steps``: under the causal
    mask past one tile the grid is ``_live_steps``' list), and which of
    the two entries it is: ``flash.token_major`` 1 for a kernel that
    indexes ``(B, T, N x H)`` where it lies, 0 for the head-major one."""
    telemetry.gauge(f"flash.rows_per_step.{kernel}", hb)
    telemetry.gauge(f"flash.grid_steps.{kernel}", steps)
    telemetry.gauge(f"flash.live_steps.{kernel}", steps)
    telemetry.gauge(f"flash.token_major.{kernel}", int(token_major))


def _fa_forward_pallas(q, k, v, causal, scale, block_q=None, block_k=None,
                       with_lse=False, interpret=False, lengths=None,
                       name=None, span=1):
    """q (B, H, T, D), k (B, Hkv, T, D), v (B, Hkv, T, Dv) with Hkv
    dividing H -> (B, H, T, Dv)[, lse (B, H, T)].  ``lengths`` (B,) int32: each batch row's
    true length (see ``_fa_kernel``'s ``bounded``); ``span``: a block
    decoder's block length (its ``span``).

    A grid step is a (q tile, k tile) pair of one KV head of one batch
    row and its query heads; without ``lengths`` (the trainer), with
    equal heads and the whole sequence in one tile, it is that pair of
    ``train_tiles`` rows of ``B * H``.  Tiles not given are
    ``train_blocks``' (the served prefill gives its own)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    hkv, tk, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // hkv
    bh = b * hkv
    qf = q.reshape(bh, g, tq, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, dv)
    if block_q is None:
        block_q, block_k = train_blocks("fwd", tq, tk, d, dv,
                                        q.dtype.itemsize, causal)
    block_q = _divisor_block(tq, min(block_q, tq))
    block_k = _divisor_block(tk, min(block_k, tk))
    nq, nk = tq // block_q, tk // block_k
    bounded = lengths is not None
    hb = 1
    if not bounded and g == 1 and nq == nk == 1:
        hb = train_tiles(bh, tq, tk, max(d, dv), q.dtype.itemsize)
    lead = () if hb == 1 else (hb,)
    # the trainer's causal grid past one tile is the list of its live
    # steps; the served prefill's, whose lengths arrive with the call,
    # stays (q tiles, k tiles)
    steps = _live_steps(nq, nk, block_q, block_k) \
        if causal and not bounded and nq * nk > 1 else None
    if not bounded:
        _say_grid("fwd", hb, len(steps[0]) if steps else nq * nk)

    if steps:  # mxlint: allow=T2 (a tuple of python ints or None)
        def q_map(b_, n, qi, kj):
            return (b_, 0, qi[n], 0)

        def kv_map(b_, n, qi, kj):
            return (b_, kj[n], 0)
    else:
        def q_map(b_, i, j, *_):
            return (b_, 0, i, 0)

        def kv_map(b_, i, j, *lens):
            if not (causal or bounded):
                return (b_, j, 0)
            # a tile that does not run is not fetched: its step asks for
            # block 0, the first the next q tile needs
            n = lens[0][b_ // hkv] if bounded else None
            return (b_, jnp.where(
                _tile_runs(i, j, n, block_q=block_q, block_k=block_k,
                           causal=causal), j, 0), 0)

    out_specs = [pl.BlockSpec((hb, g, block_q, dv), q_map)]
    out_shape = [_pallas_out_shape((bh, g, tq, dv), q.dtype, q, k, v)]
    if with_lse and hb == 1:
        # a trailing singleton: mosaic requires the last two block dims
        # (8, 128)-aligned or equal to the array's, which a 2-D
        # (1, block_q) cannot be
        out_specs.append(pl.BlockSpec((1, g, block_q, 1), q_map))
        out_shape.append(
            _pallas_out_shape((bh, g, tq, 1), jnp.float32, q, k, v))
    elif with_lse:
        # that layout lies one number a 128-lane tile in HBM: a block of
        # (128, 1) is 64 KB of DMA for 512 bytes, a third of what a
        # one-tile step of a row moves.  Several rows a step go along
        # lanes, (hb, g, block_q) of (bh, g, tq): the middle axis is
        # whole, so any hb is legal.  Not at hb == 1: past one tile the
        # relayout at every q tile's end costs the forward 6-9% (T =
        # 1,024 and 2,048; PERF.md, PR 36)
        out_specs.append(pl.BlockSpec((hb, g, block_q),
                                      lambda b_, i, j, *_: (b_, 0, i)))
        out_shape.append(
            _pallas_out_shape((bh, g, tq), jnp.float32, q, k, v))
    out = pl.pallas_call(
        functools.partial(_fa_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, nk=nk, kv_heads=hkv,
                          with_lse=with_lse, bounded=bounded, span=span,
                          hb=hb, listed=steps is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 if steps else int(bounded),
            grid=(bh, len(steps[0])) if steps else (bh // hb, nq, nk),
            in_specs=[
                pl.BlockSpec((hb, g, block_q, d), q_map),
                pl.BlockSpec((hb, block_k, d), kv_map),
                pl.BlockSpec((hb, block_k, dv), kv_map),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((*lead, g * block_q, 1), jnp.float32),   # m
                pltpu.VMEM((*lead, g * block_q, 1), jnp.float32),   # l
                pltpu.VMEM((*lead, g * block_q, dv), jnp.float32),  # acc
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel",) * (1 if steps else 2) + ("arbitrary",)),
        name=name,
        interpret=interpret,
    )(*(jnp.asarray(x, jnp.int32)
        for x in (steps or ((lengths,) if bounded else ()))),
      qf, kf, vf)
    if with_lse:
        return out[0].reshape(b, h, tq, dv), out[1].reshape(b, h, tq)
    return out[0].reshape(b, h, tq, dv)


# --- the serving prefill's entry --------------------------------------------

#: shortest prompt bucket the served prefill sends through the kernel.
#: On the v5e (PERF.md, PR 29) the two-layer prefill program at
#: Mistral-7B's widths reads, dense / kernel, 2.62 / 2.65 ms at 128,
#: 3.04 / 3.02 at 256, 4.44 / 4.34 at 512, 9.06 / 7.26 at 1,024, 20.4 /
#: 13.0 at 2,048 and 54.1 / 25.7 at 4,096: a bucket of 128 gains
#: nothing, keeps ``masked_attention`` (its scores are 2 MB) and is
#: spared the kernel's lowering at every start of a server
PREFILL_MIN_LEN = 256


def prefill_applicable(platform, mesh, head_dim, seq_len):
    """Whether a served prefill of ``seq_len`` padded positions runs the
    forward kernel instead of ``ops.attention.masked_attention``, from
    what the caller observes: the platform its weights live on, the
    engine's mesh (a sharded call would need the ``shard_map`` wrapper:
    it keeps the dense path) and the shapes Mosaic tiles without
    padding rows."""
    return (platform == "tpu" and mesh is None
            and head_dim in (64, 128, 256)
            and seq_len >= PREFILL_MIN_LEN and seq_len % 128 == 0)


def prefill_tiles(group, seq_len):
    """(block_q, block_k) of the served prefill's kernel for ``group``
    query heads a KV head: a score tile of 1,024 rows (the group's
    ``block_q`` rows each) by 1,024 keys.

    On the v5e at 4 x 8 heads of 128 in bf16, TFLOP/s of the causal
    half at 4,096 / 2,048 / 1,024 (PERF.md, PR 29): 128 x 128 17 / 16 /
    13; 256 x 256 31 / 27 / 21; 256 x 512 56 / 45 / 28; 512 x 512 70 /
    54 / 32; 128 x 1,024 88 / 62 / 35; **256 x 1,024 96 / 67 / 36**;
    256 x 2,048 79 / 52; 128 x 4,096 61.  A grid step costs about 3.8
    ns a score row whatever the keys' width (the row reductions and the
    rescale of the accumulator), so wide key tiles win until the
    diagonal tile's masked half outweighs them; 512 x 1,024 needs more
    VMEM than a kernel gets by default.  At heads of 64, 256 x 512
    reads 29 / 23 / 15 and 256 x 1,024 49 / 34 / 18."""
    return min(max(128, 1024 // group), seq_len), min(1024, seq_len)


def _prefill_flash_attention(q, k, v, lengths, interpret=False, span=1):
    """Causal attention of whole prompts, GQA inside the kernel: ``q``
    (B, H, Lp, hd), ``k`` / ``v`` (B, Hkv, Lp, hd) after RoPE,
    ``lengths`` (B,) int32 true lengths -> (B, H, Lp, hd).  Rows below
    a length are ``masked_attention``'s under ``tril``; padded rows are
    finite and read by nothing.  ``span`` (static): a block decoder's
    block length, which divides the lengths: a row then also sees the
    rest of its own block (``_fa_kernel``)."""
    hd, lp = q.shape[-1], q.shape[2]
    bq, bk = prefill_tiles(q.shape[1] // k.shape[1], lp)
    return _fa_forward_pallas(
        q, k, v, True, 1.0 / float(np.sqrt(hd)), bq, bk,
        lengths=lengths, name="prefill_flash_attention",
        interpret=interpret, span=span)


#: jitted, so that the layers of a prefill program share one trace and one
#: Mosaic lowering of the kernel, as ``ops.paged_attention`` does
prefill_flash_attention = jax.jit(_prefill_flash_attention,
                                  static_argnames=("interpret", "span"))


# --- pallas backward kernels -------------------------------------------------
# Standard two-kernel TPU flash backward (the same split
# jax.experimental.pallas.ops.tpu.flash_attention uses): a dq kernel
# sweeping K blocks innermost, and a dkv kernel sweeping Q blocks
# innermost — no atomics needed, each output block is owned by exactly
# one grid row.  p is recomputed from the saved per-row lse (written by
# the forward kernel), delta = rowsum(dO * O) is a cheap fused
# elementwise computed outside.

def _stat(ref, blk, as_row):
    """A statistics block (``lse`` or ``delta``), which travels along
    lanes (``_fa_backward_pallas``), beside scores laid keys by queries
    (``as_row``: a row ``(..., 1, block_q)``, as it arrives) or queries
    by keys (a column ``(..., block_q, 1)``, laid out here)."""
    x = ref[blk]
    return x if as_row else x[..., 0, :][..., None]


def _bwd_p(s, lse, guard):
    """The probabilities again from the scores and the saved log-sum-exp.
    ``guard``: a row may have seen no key and carry ``lse = -inf``; its
    probabilities are zeroed explicitly.  Without it every ``lse`` is
    finite, and a masked score's ``exp(-inf - lse)`` is the zero it
    needs."""
    if not guard:
        return jnp.exp(s - lse)
    return jnp.where(jnp.isfinite(s) & jnp.isfinite(lse),
                     jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)),
                     0.0)


def _fa_bwd_dq_kernel(*refs, block_q, block_k, causal, scale, nk, hb=1,
                      guard=True, listed=False):
    from jax.experimental import pallas as pl

    blk = 0 if hb == 1 else slice(None)
    if listed:
        # grid (bh, live steps): the step's tiles are read from the list
        qi_ref, kj_ref, *refs = refs
        qi, kj = qi_ref[pl.program_id(1)], kj_ref[pl.program_id(1)]
        last = jnp.minimum(((qi + 1) * block_q - 1) // block_k, nk - 1)
    else:
        qi = pl.program_id(1)
        kj = pl.program_id(2)
        last = nk - 1
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     acc_ref) = refs

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[blk].astype(jnp.float32)
        k = k_ref[blk].astype(jnp.float32)
        v = v_ref[blk].astype(jnp.float32)
        do = do_ref[blk].astype(jnp.float32)
        lse = _stat(lse_ref, blk, False)
        delta = _stat(delta_ref, blk, False)
        s = _dot(q, k, 1, 1) * scale
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        p = _bwd_p(s, lse, guard)
        dp = _dot(do, v, 1, 1)
        ds = p * (dp - delta) * scale
        acc_ref[...] += _dot(ds, k, 1, 0)

    # every step of either grid computes (a causal grid past one tile is
    # listed); the comparison that always holds is the text the one-tile
    # program had
    if listed:
        compute()
    else:
        pl.when(kj == kj)(compute)

    @pl.when(kj == last)
    def _finish():
        dq_ref[blk] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(*refs, block_q, block_k, causal, scale, nq, hb=1,
                       guard=True, listed=False):
    from jax.experimental import pallas as pl

    blk = 0 if hb == 1 else slice(None)
    if listed:
        ki_ref, qj_ref, *refs = refs
        ki, qj = ki_ref[pl.program_id(1)], qj_ref[pl.program_id(1)]
        first = jnp.minimum(ki * block_k // block_q, nq - 1)
    else:
        ki = pl.program_id(1)
        qj = pl.program_id(2)
        first = 0
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = refs

    @pl.when(qj == first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[blk].astype(jnp.float32)
        k = k_ref[blk].astype(jnp.float32)
        v = v_ref[blk].astype(jnp.float32)
        do = do_ref[blk].astype(jnp.float32)
        lse = _stat(lse_ref, blk, True)
        delta = _stat(delta_ref, blk, True)
        st = _dot(k, q, 1, 1) * scale
        if causal:
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            qpos = qj * block_q + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(qpos >= kpos, st, -jnp.inf)
        pt = _bwd_p(st, lse, guard)
        dv_acc[...] += _dot(pt, do, 1, 0)
        dpt = _dot(v, do, 1, 1)
        dst = pt * (dpt - delta) * scale
        dk_acc[...] += _dot(dst, q, 1, 0)

    if listed:      # as in dq: every step computes
        compute()
    else:
        pl.when(qj == qj)(compute)

    @pl.when(qj == nq - 1)
    def _finish():
        dk_ref[blk] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[blk] = dv_acc[...].astype(dv_ref.dtype)


def _fa_backward_pallas(q, k, v, o, do, lse, causal, scale, block_q=None,
                        block_k=None, interpret=False):
    """dq/dk/dv via the two pallas kernels; (B, H, T, D) in and out,
    ``v``, ``o``, ``do`` and ``dv`` (B, H, T, Dv).
    A grid step of either is a (q tile, k tile) pair of one head of one
    batch row, each kernel at its own tiles (``train_blocks``; both at
    ``block_q`` x ``block_k`` where those are given), or of
    ``train_tiles`` rows of ``B * H`` where the whole sequence is one
    tile; float32 operands in all five products (the stored dtype
    instead, bf16 into the MXU, read the same step time at
    ``(4, 32, 4096, 192 / 128)`` causal: 758.4 against 759.1 ms, PERF.md,
    PR 44).  Past one tile a causal grid is the list of its live steps
    (``_live_steps``), so no step lies above the diagonal.  Every live
    tile is masked: the forward's split, the mask only where the
    diagonal crosses, was worth 0.02-0.05 ms of 11 here (PERF.md, PR
    45)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[-1]
    bh = b * h
    qf = q.reshape(bh, tq, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, dv)
    dof = do.reshape(bh, tq, dv)

    def blocks(kernel):
        bq, bk = (block_q, block_k) if block_q is not None else \
            train_blocks(kernel, tq, tk, d, dv, q.dtype.itemsize, causal)
        return (_divisor_block(tq, min(bq, tq)),
                _divisor_block(tk, min(bk, tk)))

    (bq_q, bk_q), (bq_kv, bk_kv) = blocks("dq"), blocks("dkv")
    one_tile = (bq_q, bk_q) == (bq_kv, bk_kv) == (tq, tk)
    hb = 1
    if one_tile:
        hb = train_tiles(bh, tq, tk, max(d, dv), q.dtype.itemsize)
    lead = () if hb == 1 else (hb,)
    # every row met key 0 (no ``lengths`` here, and the mask is on
    # positions), so every lse is finite; a step of several rows keeps
    # its guard with the rest of its text
    guard = hb > 1 or tq != tk
    # the statistics along lanes, (bh, 1, T): a trailing singleton lies
    # one number a 128-lane tile in HBM, and dkv, whose statistics
    # follow its innermost index, fetched 512 KiB of padding a step for
    # 4 KiB of numbers.  The forward writes lse as it did (its relayout
    # at every q tile's end cost it 6-9%, PR 36); this reshape is XLA's
    stat_shape = (bh, 1, tq)
    lsef = lse.reshape(stat_shape)
    # delta = rowsum(dO * O): one fused elementwise pass outside the
    # kernels (XLA fuses it into the surrounding graph)
    delta = (dof.astype(jnp.float32) *
             o.reshape(bh, tq, dv).astype(jnp.float32)).sum(-1).reshape(
                 stat_shape)

    def call(name, kernel, bq, bk, blocks_in, blocks_out, out_shape,
             scratch):
        """One backward kernel at tiles ``bq`` x ``bk`` over its grid
        ``(bh / hb, outer tiles, inner tiles)``, a step's tile pair its
        two inner indices (``dq``: q tiles outside; ``dkv``: k tiles);
        under the causal mask past one tile over ``(bh, live steps)``
        instead, the pair read from ``_live_steps``' two
        scalar-prefetched lists (at ``tq == tk``, as every caller has
        it: every k tile then has a live q tile to be written from).  ``blocks_*``: (block shape, which of a
        step's two tiles it follows: 0 the outer, 1 the inner, and the
        axis that tile indexes)."""
        q_outside = name == "dq"
        nq_, nk_ = tq // bq, tk // bk
        steps = _live_steps(nq_, nk_, bq, bk, q_outside) \
            if causal and tq == tk and nq_ * nk_ > 1 else None
        _say_grid(name, hb, len(steps[0]) if steps else nq_ * nk_)

        def at(which, axis):
            # index-map arguments: (b, outer, inner), or listed
            # (b, step, outer tiles' list, inner tiles' list)
            def index(b_, *rest):
                t = rest[1 + which][rest[0]] if steps else rest[which]
                return (b_, t, 0) if axis == 1 else (b_, 0, t)
            return index

        in_specs, out_specs = (
            [pl.BlockSpec(shape, at(which, axis))
             for shape, which, axis in blocks]
            for blocks in (blocks_in, blocks_out))
        if len(out_specs) == 1:
            out_specs, = out_specs
        inner = {"nk": nk_} if q_outside else {"nq": nq_}
        return pl.pallas_call(
            functools.partial(kernel, block_q=bq, block_k=bk, causal=causal,
                              scale=scale, hb=hb, guard=guard,
                              listed=steps is not None, **inner),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2 if steps else 0,
                grid=(bh, len(steps[0])) if steps else
                (bh // hb, *((nq_, nk_) if q_outside else (nk_, nq_))),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel",) * (1 if steps else 2) + ("arbitrary",)),
            interpret=interpret,
        )(*(jnp.asarray(x, jnp.int32) for x in steps or ()),
          qf, kf, vf, dof, lsef, delta)

    # dq: K innermost; q/do/lse/delta and dq follow the q tile
    dq = call(
        "dq", _fa_bwd_dq_kernel, bq_q, bk_q,
        [((hb, bq_q, d), 0, 1), ((hb, bk_q, d), 1, 1),
         ((hb, bk_q, dv), 1, 1), ((hb, bq_q, dv), 0, 1),
         ((hb, 1, bq_q), 0, 2), ((hb, 1, bq_q), 0, 2)],
        [((hb, bq_q, d), 0, 1)],
        _pallas_out_shape((bh, tq, d), q.dtype, q, k, v, do),
        [pltpu.VMEM((*lead, bq_q, d), jnp.float32)])
    # dkv: Q innermost; k/v and dk/dv follow the k tile
    dk, dv_out = call(
        "dkv", _fa_bwd_dkv_kernel, bq_kv, bk_kv,
        [((hb, bq_kv, d), 1, 1), ((hb, bk_kv, d), 0, 1),
         ((hb, bk_kv, dv), 0, 1), ((hb, bq_kv, dv), 1, 1),
         ((hb, 1, bq_kv), 1, 2), ((hb, 1, bq_kv), 1, 2)],
        [((hb, bk_kv, d), 0, 1), ((hb, bk_kv, dv), 0, 1)],
        [_pallas_out_shape((bh, tk, d), k.dtype, q, k, v, do),
         _pallas_out_shape((bh, tk, dv), v.dtype, q, k, v, do)],
        [pltpu.VMEM((*lead, bk_kv, d), jnp.float32),
         pltpu.VMEM((*lead, bk_kv, dv), jnp.float32)])
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv_out.reshape(b, h, tk, dv))


# --- the token-major entry ----------------------------------------------------
# The trainer's projections leave q, k and v as (B, T, N x H), and the
# output projection wants o the same way.  The kernels above take
# (B, N, T, H): 8 transposes a layer in HBM (q, k, v in and o out, do in
# and dq, dk, dv out), each of which reads or writes heads of 64 padded to
# a 128-lane tile.  The three kernels below index (B, T, N x H) where it
# lies: a block is ``(bb, T, 128)``, ``bb`` batch rows of the whole
# sequence by one lane tile, which holds two heads of 64 (or one head,
# where a head is a multiple of 128 wide).

def tokens_lanes(d):
    """(lanes of a token-major block, heads it holds) at heads ``d``
    wide, or None where heads do not fill whole lane tiles: two heads of
    64 to a tile of 128, one head of a multiple of 128."""
    if d == 64:
        return 128, 2
    return (d, 1) if d % 128 == 0 else None


def tokens_rows(b, pair, tq, tk, d, itemsize=2):
    """Batch rows ``bb`` of a token-major grid step: ``train_tiles``'
    VMEM rule counted in (batch, head) rows, ``pair`` of them a batch
    row, so that a step holds the rows the head-major one does (BERT's
    ``(128, 128, 12 x 64)``: 8 batch rows of two heads, 16 rows).

    On the v5e at that shape in bf16, ms a layer-call, forward / dq / dkv
    (PERF.md, PR 50; ``chiprun -- python tools/prefill_flash_bench.py
    --train 128,12,128,64 --layout tokens --rows 4,8,16,32``; the
    head-major kernels at 16 rows in the same call read 0.520 / 0.427 /
    0.526): 4 rows 0.421 / 0.520 / 0.408, 8 rows 0.298 / 0.332 / 0.337,
    **16 rows 0.268 / 0.289 / 0.330**, 32 rows 0.275 / 0.282 / 0.329.
    Half the DMA bytes a row (no head of 64 padded to 128 lanes) and no
    accumulator in VMEM; ``dq`` and ``dkv`` take ``delta`` themselves.
    Tried and within 2% of that, so not taken: the lane masks as one row
    broadcast (0.271 / 0.274 / 0.339) or as a product with 0 / 1 (0.271
    / 0.299 / 0.352), the forward's two ``p v`` as one product 2T deep
    (0.263), the masks on ``q`` as an AND on packed words with both
    (0.244 / 0.291 / 0.335)."""
    cap = TRAIN_VMEM_BYTES // (pair * train_row_bytes(tq, tk, d, itemsize))
    return max(c for c in range(1, max(1, min(cap, b)) + 1) if b % c == 0)


def tokens_applicable(q, k, v):
    """Whether ``ops.attention.sdpa_raw``'s unmasked call on ``q``, ``k``,
    ``v`` (B, T, N, H) takes ``flash_attention_tokens``: read from the
    operands' own shapes and from where the program runs, no knob.  Equal
    shapes; heads that fill lane tiles (64 wide and an even number of
    them, or a multiple of 128 up to 256); a sequence that is a multiple
    of 128 and one tile (up to 512, where ``train_tiles`` takes several
    rows a step; past one tile the rule refuses: no cell trains through
    ``sdpa_raw`` there, and the head-major kernels keep their tiles and
    live-step lists); the Pallas forward and backward both on
    (``_pallas_applicable``, ``_pallas_bwd_enabled``: the switches of the
    head-major kernels); no mesh and no ``shard_map`` around the call (a
    Mosaic call is not partitioned, and ``_pallas_maybe_sharded``'s
    wrapper shards heads, which here share a lane tile)."""
    from ..parallel import current_mesh

    if not (q.shape == k.shape == v.shape and q.ndim == 4):
        return False
    _, t, n, d = q.shape
    lanes = tokens_lanes(d)
    if lanes is None or d > 256 or n % lanes[1] or t % 128 or t > 512:
        return False
    mesh = current_mesh()
    if (mesh is not None and mesh.size > 1) or _inside_shard_map():
        return False
    rows = jax.ShapeDtypeStruct((t, d), q.dtype)
    return _pallas_applicable(rows, rows) and _pallas_bwd_enabled()


def _head_lanes(shape, pair, d):
    """The lane masks of a tile's ``pair`` heads, head ``j`` in lanes
    ``[j d, (j + 1) d)`` (None for a tile that is one head)."""
    if pair == 1:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return [(lane >= j * d) & (lane < (j + 1) * d) for j in range(pair)]


def _only(x, lanes):
    """``x`` with the lanes outside ``lanes`` zeroed: a head of a tile
    reached by a mask, not by a slice at lane 64.  A product that
    contracts the tile's 128 lanes then sees the one head (the v5e's MXU
    contracts 128 deep whatever a head's width)."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _one_tile_mask(s, causal, keys_by_queries=False):
    """Scores of a whole sequence under the causal mask."""
    if not causal:
        return s
    a = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    b = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where((b >= a) if keys_by_queries else (a >= b), s, -jnp.inf)


def _fa_tokens_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, causal,
                          scale, pair, d):
    """A step: ``bb`` batch rows of one lane tile's heads, the whole
    sequence.  Each head's arithmetic is ``_fa_kernel``'s at one tile:
    bf16 operands into the products, float32 scores, softmax and
    result."""
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    bb, t, _ = q.shape
    out = None
    for j, lanes in enumerate(_head_lanes(q.shape, pair, d)):
        s = _one_tile_mask(_dot(_only(q, lanes), k, 1, 1) * scale, causal)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        # p_j v fills every lane; the head's own are kept
        o = _dot(p.astype(v.dtype), v, 1, 0) / jnp.maximum(l, 1e-30)
        out = o if out is None else jnp.where(lanes, o, out)
        if lse_ref:
            # along lanes, a row of T numbers a (batch, head)
            lse_ref[0][:, 0, j:j + 1, :] = (
                m + jnp.log(jnp.maximum(l, 1e-30))).reshape(bb, 1, t)
    o_ref[...] = out.astype(o_ref.dtype)


def _head_delta(do_o, lanes):
    """``delta`` = rowsum(dO * O) of one head of the tile, a column
    ``(bb, T, 1)``: the head's lanes of the product ``do_o``, summed.
    Inside the kernels, which read ``o`` for it: a sum over 64 of 768
    minor lanes is no reduction XLA has (it wrote the float32 product to
    HBM, changed its layout and read it again)."""
    return _only(do_o, lanes).sum(axis=-1, keepdims=True)


def _fa_tokens_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                         *, causal, scale, pair, d):
    """``_fa_bwd_dq_kernel``'s arithmetic a head (float32 operands in the
    three products), two heads to a lane tile."""
    q, k, v, o, do = (r[...].astype(jnp.float32)
                      for r in (q_ref, k_ref, v_ref, o_ref, do_ref))
    dq, do_o = None, do * o
    for j, lanes in enumerate(_head_lanes(q.shape, pair, d)):
        lse = lse_ref[:, 0, j:j + 1, :][..., 0, :][..., None]
        s = _one_tile_mask(_dot(_only(q, lanes), k, 1, 1) * scale, causal)
        p = jnp.exp(s - lse)
        dp = _dot(_only(do, lanes), v, 1, 1)
        ds = p * (dp - _head_delta(do_o, lanes)) * scale
        dqj = _dot(ds, k, 1, 0)
        dq = dqj if dq is None else jnp.where(lanes, dqj, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _fa_tokens_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dk_ref, dv_ref, *, causal, scale, pair, d):
    """``_fa_bwd_dkv_kernel``'s arithmetic a head, scores laid keys by
    queries; a head's ``dk`` and ``dv`` land in its own lanes because the
    products' right operands (``q``, ``do``) are zero in the others."""
    q, k, v, o, do = (r[...].astype(jnp.float32)
                      for r in (q_ref, k_ref, v_ref, o_ref, do_ref))
    bb, t, _ = q.shape
    dk, dv, do_o = None, None, do * o
    for j, lanes in enumerate(_head_lanes(q.shape, pair, d)):
        lse = lse_ref[:, 0, j:j + 1, :]
        delta = _head_delta(do_o, lanes).reshape(bb, 1, t)
        qj, doj = _only(q, lanes), _only(do, lanes)
        st = _one_tile_mask(_dot(k, qj, 1, 1) * scale, causal, True)
        pt = jnp.exp(st - lse)
        dvj = _dot(pt, doj, 1, 0)
        dpt = _dot(v, doj, 1, 1)
        dst = pt * (dpt - delta) * scale
        dkj = _dot(dst, qj, 1, 0)
        dk, dv = (dkj, dvj) if dk is None else (dk + dkj, dv + dvj)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _tokens_call(name, kernel, operands, stats, like, heads, causal, scale,
                 with_lse=False, interpret=False):
    """One token-major kernel over its grid ``(B / bb, N x H / 128)``:
    ``operands`` (B, T, N x H) in blocks ``(bb, T, 128)`` and ``stats``
    (B, N / pair, pair, T) in blocks ``(bb, 1, pair, T)`` (a row of ``T``
    numbers a (batch, head), the ``pair`` heads of a lane tile side by
    side) in; out a result like each of ``like`` and, ``with_lse``, the
    statistics."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, width = operands[0].shape
    d = width // heads
    lanes, pair = tokens_lanes(d)
    bb = tokens_rows(b, pair, t, t, d, operands[0].dtype.itemsize)
    _say_grid(name, bb * pair, 1, token_major=True)
    block = pl.BlockSpec((bb, t, lanes), lambda i, j: (i, 0, j))
    stat = pl.BlockSpec((bb, 1, pair, t), lambda i, j: (i, j, 0, 0))
    out_specs = [block] * len(like) + [stat] * with_lse
    out_shape = [_pallas_out_shape((b, t, width), x.dtype, *operands)
                 for x in like] + [_pallas_out_shape(
                     (b, heads // pair, pair, t), jnp.float32,
                     *operands)] * with_lse
    one = len(out_shape) == 1       # as the head-major dq: no tuple of one
    return pl.pallas_call(
        functools.partial(kernel, causal=causal, scale=scale, pair=pair,
                          d=d),
        grid=(b // bb, width // lanes),
        in_specs=[block] * len(operands) + [stat] * len(stats),
        out_specs=out_specs[0] if one else out_specs,
        out_shape=out_shape[0] if one else out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret)(*operands, *stats)


def _fa_forward_tokens(q, k, v, heads, causal, scale, with_lse=False,
                       interpret=False):
    """q, k, v (B, T, N x H) as the projections leave them -> o the
    same[, lse (B, N / pair, pair, T) float32]."""
    out = _tokens_call("fwd", _fa_tokens_fwd_kernel, (q, k, v), (), (q,),
                       heads, causal, scale, with_lse, interpret)
    return tuple(out) if with_lse else out


def _fa_backward_tokens(q, k, v, o, do, lse, heads, causal, scale,
                        interpret=False):
    """dq, dk, dv (B, T, N x H) by two kernels, as the head-major
    backward: ``dq`` one result, ``dkv`` two, all in the operands' dtype.
    Both read ``o`` and take ``delta`` = rowsum(dO * O) a head
    themselves."""
    operands = (q, k, v, o, do)
    dq = _tokens_call("dq", _fa_tokens_dq_kernel, operands, (lse,), (q,),
                      heads, causal, scale, interpret=interpret)
    dk, dv = _tokens_call("dkv", _fa_tokens_dkv_kernel, operands, (lse,),
                          (k, v), heads, causal, scale, interpret=interpret)
    return dq, dk, dv


def _tokens_scale(q, heads, scale):
    return float(scale) if scale is not None else \
        1.0 / float(np.sqrt(q.shape[-1] // heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_tokens(q, k, v, heads, causal=False, scale=None):
    """q, k, v (B, T, N x H), ``heads`` = N -> (B, T, N x H): attention a
    head with no operand or result transposed in HBM, in the forward or
    in the backward.  For callers that hold token-major operands and
    have asked ``tokens_applicable``; there is no fall-back here."""
    return _fa_forward_tokens(q, k, v, heads, causal,
                              _tokens_scale(q, heads, scale))


def _tokens_fwd(q, k, v, heads, causal, scale):
    o, lse = _fa_forward_tokens(q, k, v, heads, causal,
                                _tokens_scale(q, heads, scale),
                                with_lse=True)
    return o, (q, k, v, o, lse)


def _tokens_bwd(heads, causal, scale, res, g):
    q, k, v, o, lse = res
    return _fa_backward_tokens(q, k, v, o, g, lse, heads, causal,
                               _tokens_scale(q, heads, scale))


flash_attention_tokens.defvjp(_tokens_fwd, _tokens_bwd)


# --- chunked jnp backward ----------------------------------------------------

def _causal_block_mask(tq, bk, j, offset=0):
    """offset = tk - tq: query i attends keys ≤ i + offset (same
    convention as _sdpa_ref's tril(k=tk-tq))."""
    qpos = lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
    kpos = j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
    return qpos + offset >= kpos


def _fa_backward(q, k, v, o, g, causal, scale, block=512):
    """Recompute-based backward scanned over K blocks — peak score memory
    is O(T·block), matching the forward kernel's promise.  Two passes:
    (1) online-softmax scan recovers lse; (2) per-block scan accumulates
    dq and emits dk/dv (standard flash-attention backward)."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    tq, tk = qf.shape[-2], kf.shape[-2]
    bk = min(block, tk)
    nk = tk // bk if tk % bk == 0 else None
    if nk is None:  # ragged tail: fall back to one-shot backward
        return _fa_backward_dense(qf, kf, vf, gf, q, k, v, causal, scale,
                                  tq, tk)
    kb = kf.reshape(*kf.shape[:-2], nk, bk, kf.shape[-1])
    vb = vf.reshape(*vf.shape[:-2], nk, bk, vf.shape[-1])
    kb = jnp.moveaxis(kb, -3, 0)   # (nk, B, H, bk, D)
    vb = jnp.moveaxis(vb, -3, 0)

    # pass 1: lse via online softmax over k blocks
    def lse_body(carry, inp):
        m, l = carry
        j, kj = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj) * scale
        if causal:
            s = jnp.where(_causal_block_mask(tq, bk, j, tk - tq), s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
        l_new = l * jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0) \
            + p.sum(-1)
        return (m_new, l_new), None

    # derived-from-q carry init: see the forward's vma note
    m0 = qf[..., 0] * 0 - jnp.inf
    l0 = qf[..., 0] * 0
    (m, l), _ = lax.scan(lse_body, (m0, l0),
                         (jnp.arange(nk), kb))
    lse = jnp.where(jnp.isfinite(m), m, 0.0) + \
        jnp.log(jnp.maximum(l, 1e-30))
    delta = (gf * o.astype(jnp.float32)).sum(-1)  # (B, H, Tq)

    # pass 2: per-block grads
    def grad_body(dq, inp):
        j, kj, vj = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj) * scale
        if causal:
            s = jnp.where(_causal_block_mask(tq, bk, j, tk - tq), s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s),
                      jnp.exp(s - lse[..., None]), 0.0)
        dvj = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vj)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kj)
        dkj = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dkj, dvj)

    dq0 = qf * 0  # derived carry init: see the forward's vma note
    dq, (dkb, dvb) = lax.scan(grad_body, dq0,
                              (jnp.arange(nk), kb, vb))
    dk = jnp.moveaxis(dkb, 0, -3).reshape(kf.shape)
    dv = jnp.moveaxis(dvb, 0, -3).reshape(vf.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_backward_dense(qf, kf, vf, gf, q, k, v, causal, scale, tq, tk):
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    delta = (p * dp).sum(-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _pallas_out_shape(shape, dtype, *operands):
    """out_shape for pallas_call that survives a CHECKED shard_map:
    inside a manual mesh, jax requires the custom-call's output to
    declare which mesh axes it varies over (vma).  The output varies
    over exactly the axes its OPERANDS do — declaring all manual axes
    instead would over-claim on a multi-axis mesh whose shard_map specs
    name only some of them (e.g. the sp-only specs of ring.py under a
    dp×sp mesh) and fail the output typecheck.  Outside shard_map the
    operands vary over nothing and this is a plain ShapeDtypeStruct."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _inside_shard_map():
    """True when tracing INSIDE a shard_map body (the abstract mesh has
    manual axes).  There the operands are already per-shard and wrapping
    another shard_map over the same mesh is invalid — the ring/ulysses
    bodies reach the flash kernel exactly this way."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def _pallas_bwd_enabled():
    import os

    return os.environ.get("MXT_PALLAS_FLASH_BWD", "1") != "0"


def _pallas_maybe_sharded(q, k, v, causal, scale, with_lse=False):
    """Route the pallas kernel under GSPMD: mosaic custom-calls cannot
    be automatically partitioned (XLA raises 'wrap the call in a
    shard_map'), so under an active multi-device mesh the kernel runs
    inside shard_map with batch over 'dp' and heads over 'tp' — the
    megatron attention layout; T stays unsharded (T-sharding is ring /
    ulysses' job, parallel/ring.py).  Caught OFFLINE via the topology
    client in round 5 — on real chips the un-wrapped kernel fails to
    compile for any dp/tp mesh.  Indivisible batch/head counts fall
    back to the chunked path, which GSPMD partitions freely.  The kernel
    bodies are independent per shard and the varying-axes checker cannot
    see through a mosaic custom-call, hence ``check_vma=False``."""
    from ..parallel import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or _inside_shard_map():
        return _fa_forward_pallas(q, k, v, causal, scale,
                                  with_lse=with_lse)
    dp = "dp" if "dp" in mesh.shape else None
    tp = "tp" if "tp" in mesh.shape else None
    if dp is None and tp is None:
        return _fa_forward_pallas(q, k, v, causal, scale,
                                  with_lse=with_lse)
    if (dp and q.shape[0] % mesh.shape[dp]) or \
            (tp and q.shape[1] % mesh.shape[tp]):
        out = _fa_forward_chunked(q, k, v, causal, scale)
        return (out, None) if with_lse else out
    from jax.sharding import PartitionSpec as P

    spec = P(dp, tp, None, None)
    return jax.shard_map(
        lambda a, b, c: _fa_forward_pallas(a, b, c, causal, scale,
                                           with_lse=with_lse),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, P(dp, tp, None)) if with_lse else spec,
        check_vma=False)(q, k, v)


def _pallas_bwd_maybe_sharded(q, k, v, o, g, lse, causal, scale):
    """Backward twin of :func:`_pallas_maybe_sharded`: same mesh
    routing, same dp/tp specs (shapes matched the forward's sharded
    decision, so divisibility holds by construction)."""
    from ..parallel import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or _inside_shard_map():
        return _fa_backward_pallas(q, k, v, o, g, lse, causal, scale)
    dp = "dp" if "dp" in mesh.shape else None
    tp = "tp" if "tp" in mesh.shape else None
    if (dp is None and tp is None) or \
            (dp and q.shape[0] % mesh.shape[dp]) or \
            (tp and q.shape[1] % mesh.shape[tp]):
        return _fa_backward_pallas(q, k, v, o, g, lse, causal, scale)
    from jax.sharding import PartitionSpec as P

    s4 = P(dp, tp, None, None)
    s3 = P(dp, tp, None)
    return jax.shard_map(
        lambda a, b, c, oo, gg, ll: _fa_backward_pallas(
            a, b, c, oo, gg, ll, causal, scale),
        mesh=mesh, in_specs=(s4, s4, s4, s4, s4, s3),
        out_specs=(s4, s4, s4),
        check_vma=False)(q, k, v, o, g, lse)


def _pallas_applicable(q, k):
    import os

    # MXT_PALLAS_FLASH=0: master kill switch to the chunked-jnp path
    # (both directions) — the operational lever when a backend update
    # changes mosaic behavior under the same framework code
    if os.environ.get("MXT_PALLAS_FLASH", "1") == "0":
        return False
    return (_on_tpu() and q.shape[-2] % 128 == 0
            and k.shape[-2] % 128 == 0 and q.shape[-2] == k.shape[-2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_raw(q, k, v, causal=False, scale=None):
    """q/k (B, H, T, D), v (B, H, T, Dv) → (B, H, T, Dv).  Pallas on TPU,
    jnp fallback."""
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    if _pallas_applicable(q, k):
        return _pallas_maybe_sharded(q, k, v, causal, scale)
    return _fa_forward_chunked(q, k, v, causal, scale)


def _fwd(q, k, v, causal, scale):
    s = float(scale) if scale is not None else \
        1.0 / float(np.sqrt(q.shape[-1]))
    if _pallas_applicable(q, k) and _pallas_bwd_enabled():
        # the pallas forward saves per-row lse so the backward can run
        # as pallas kernels too (VMEM-resident scores, no HBM
        # (T, block) slabs); lse is None when the sharded wrapper fell
        # back to chunked (indivisible batch/heads)
        o, lse = _pallas_maybe_sharded(q, k, v, causal, s,
                                       with_lse=True)
        return o, (q, k, v, o, lse)
    o = flash_attention_raw(q, k, v, causal, scale)
    return o, (q, k, v, o, None)


def _bwd(causal, scale, res, g):
    q, k, v, o, lse = res
    s = float(scale) if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
    if lse is not None:
        return _pallas_bwd_maybe_sharded(q, k, v, o, g, lse, causal, s)
    return _fa_backward(q, k, v, o, g, causal, s)


flash_attention_raw.defvjp(_fwd, _bwd)


def train_form(q_shape, dv=None, itemsize=2, causal=False, layout="heads"):
    """Which form ``flash_attention_raw`` and its backward take for ``q``
    (B, H, T, D) (``k`` alike) and values ``dv`` wide, here and now, as one
    word for a log: ``pallas:fwd<block_q>x<block_k>,dq<..>,dkv<..>:d<D>/
    <Dv>:hb<rows a step>`` or ``chunked``.  ``layout="tokens"``: the form
    ``ops.attention.sdpa_raw`` takes for ``q`` (B, T, N, H) as it holds
    it; where ``tokens_applicable`` gives it the token-major entry the
    word ends ``:tokens`` (``hb`` still (batch, head) rows), else it is
    the head-major word of the transposed shape."""
    if layout == "tokens":
        b, t, h, d = q_shape
        q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
        if dv in (None, d) and tokens_applicable(q, q, q):
            pair = tokens_lanes(d)[1]
            hb = tokens_rows(b, pair, t, t, d, itemsize) * pair
            return (f"pallas:fwd{t}x{t},dq{t}x{t},dkv{t}x{t}:d{d}/{d}"
                    f":hb{hb}:tokens")
        q_shape = (b, h, t, d)
    b, h, t, d = q_shape
    dv = d if dv is None else dv
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    if not (_pallas_applicable(q, q) and _pallas_bwd_enabled()):
        return "chunked"
    tiles = [train_blocks(kern, t, t, d, dv, itemsize, causal)
             for kern in ("fwd", "dq", "dkv")]
    hb = train_tiles(b * h, t, t, max(d, dv), itemsize) \
        if tiles == [(t, t)] * 3 else 1
    word = ",".join(f"{kern}{bq}x{bk}" for kern, (bq, bk)
                    in zip(("fwd", "dq", "dkv"), tiles))
    return f"pallas:{word}:d{d}/{dv}:hb{hb}"


def flash_attention(query, key, value, causal=False, scale=None, **kwargs):
    """NDArray-level op: fused attention over (B, H, T, D) operands.
    Platform routing rides apply_op's dispatch-platform hint."""
    from .registry import apply_op

    return apply_op(
        lambda q, k, v: flash_attention_raw(q, k, v, causal, scale),
        query, key, value, name="flash_attention")

#!/usr/bin/env python
"""The pieces of a selecting K/V layer alone, on the chip, at Keye-VL-2.0's
head sizes (32 query / 4 KV heads of 128, an indexer of 16 x 64 that
selects 2,048; ``keye_vl2.longctx_decode_sat``):

* a STEP's, 16 slots through a block table of 32,768 positions, at
  contexts of 8k-28k (the cell's) and at 2k-4k (where a walk to the
  table's width would show): the index keys scored in chunks up to the
  longest live slot (``sparse_select.window_select``) against one gather
  of the whole table (``SCORE_CHUNK`` = the table; alone the two read
  alike, in the cell's step the chunks are 1.1 ms of 20.6 faster:
  PERF.md section 6, PR 51), ``lax.top_k`` alone, the gather of the
  selected K and V rows with the attention over them
  (``paged_attention.selected_rows`` under ``gqa_selected_attention``),
  and the three together;
* the selected rows brought by a COPY A ROW (``--parts dma``): a minimal
  Pallas kernel, a slot a grid step, whose 2,048 rows of 1 KiB come from
  the pool in HBM into VMEM one ``make_async_copy`` each, 4 to 32 in
  flight, from one pool and from two; nothing is computed.  Its ns a row
  against the XLA gather's say whether a ``selected_decode_attention``
  kernel can bring its rows faster than XLA does (PERF.md section 6,
  PR 51);
* a prefill TILE's (128 query rows) at causal extents of 8k, 16k and
  28k: the scoring, then the two forms of the tile's attention: the
  selection as a mask (two bisections) over the extent's rows in order
  (``gqa_masked_attention``: what ``kv_causal_attention`` runs), or
  ``lax.top_k`` and a gather of the 2,048 selected rows a query
  (``gqa_selected_attention``).

    chiprun -- python tools/sparse_select_bench.py [--parts step,tile,dma]

Each timing is one jitted call run ``REPS`` times after a warm-up, the
median.  Lines of JSON to stdout and
``chiprun_out/sparse_select_bench.jsonl``.  There is no CPU mode: a CPU
time is no reading of the chip.
"""
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

REPS = 9
TOPK = 2048
NH, NKV, HD, IH, ID = 32, 4, 128, 16, 64


def _time(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(REPS):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out) * 1e3


def fetch_rows(pools, at, inflight, interpret=False):
    """``at`` (S, k) row numbers -> a tuple of (S * k, 1, words): those rows
    of each of ``pools`` ((N, 1, words) uint32, in HBM), one copy a row a
    pool into the output's block in VMEM, ``inflight`` rows' copies
    started before the first is waited for.  The prototype of a kernel's
    fetch: it computes nothing.  A row is a stored tile of its own (``(N,
    1, words)`` lies ``T(1,128)``): Mosaic refuses a one-row slice of a
    ``(N, words)`` array in HBM ("must be aligned to tiling (8)"), and of
    a flat one (tiling 1,024 words), so a kernel that copies a row needs
    the pools stored a token a tile, which the served pools are not
    (``(blocks, 1, 16, 512)`` bf16: 16 tokens to a tile row)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, k = at.shape
    n = len(pools)
    words = pools[0].shape[-1]

    def kernel(at_ref, *refs):
        hbm, out, sem = refs[:n], refs[n:2 * n], refs[2 * n]
        slot = pl.program_id(0)

        def copies(j):
            row = at_ref[slot * k + j]
            return [pltpu.make_async_copy(
                hbm[i].at[row], out[i].at[j], sem.at[i, j % inflight])
                for i in range(n)]

        def start(j, c):
            for copy in copies(j):
                copy.start()
            return c

        def turn(j, c):
            for copy in copies(j):
                copy.wait()

            @pl.when(j + inflight < k)
            def _next():
                start(j + inflight, 0)

            return c

        lax.fori_loop(0, inflight, start, 0)
        lax.fori_loop(0, k, turn, 0)

    block = pl.BlockSpec((k, 1, words), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=[block] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n, inflight))]),
        out_shape=[jax.ShapeDtypeStruct((s * k, 1, words), jnp.uint32)] * n,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="fetch_selected_rows", interpret=interpret,
    )(at.reshape(-1).astype(jnp.int32), *pools)


def main():
    import argparse

    import jax
    import jax.numpy as jnp
    import numpy as np

    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", default="step,tile,dma")
    parts = ap.parse_args().parts.split(",")

    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops import sparse_select as ss

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; jax found {dev.platform!r}")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/sparse_select_bench.jsonl", "a")

    def say(**row):
        line = json.dumps(row, sort_keys=True)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    bf = jnp.bfloat16
    s, bs, t = 16, 16, 32768
    mb = t // bs
    for lo, hi in ((8192, 28671), (2048, 4095)) if "step" in parts else ():
        pos = np.linspace(lo, hi, s).astype(np.int32)
        own = -(-(pos + 1) // bs)
        nb = int(own.sum()) + 7
        perm = np.random.RandomState(3).permutation(nb)
        tables, start = np.full((s, mb), nb, np.int32), 0
        for i in range(s):
            tables[i, :own[i]] = perm[start:start + own[i]]
            start += own[i]
        ks = jax.random.split(jax.random.PRNGKey(5), 6)
        k_pool = jax.random.normal(ks[0],
                                   pa.pool_shape(nb, NKV, HD, bs, NKV), bf)
        v_pool = jax.random.normal(ks[1], k_pool.shape, bf)
        i_pool = jax.random.normal(ks[2], ss.index_pool_shape(nb, bs, ID), bf)
        q = jax.random.normal(ks[3], (s, NH, HD), bf)
        q_idx = jax.random.normal(ks[4], (s, IH, ID), bf)
        w_idx = jax.random.normal(ks[5], (s, IH), bf) * 0.02
        tables_d, pos_d = jnp.asarray(tables), jnp.asarray(pos)

        def select(i_pool, q_idx, w_idx):
            win = pa.window(i_pool, tables_d, pos_d, t, False)
            return ss.window_select(q_idx, w_idx, i_pool, win, TOPK)

        def attend(k_pool, v_pool, q, idx, valid):
            win = pa.window(k_pool, tables_d, pos_d, t, False)
            return ss.gqa_selected_attention(
                q, pa.selected_rows(k_pool, win, idx),
                pa.selected_rows(v_pool, win, idx), valid)

        def whole(k_pool, v_pool, i_pool, q, q_idx, w_idx):
            return attend(k_pool, v_pool, q, *select(i_pool, q_idx, w_idx))

        row = {"what": "step", "slots": s, "contexts": [lo, hi]}
        row["select_chunked_ms"] = _time(jax.jit(select), i_pool, q_idx,
                                         w_idx)
        chunk = ss.SCORE_CHUNK
        ss.SCORE_CHUNK = t
        row["select_whole_table_ms"] = _time(jax.jit(select), i_pool, q_idx,
                                             w_idx)
        ss.SCORE_CHUNK = chunk
        scores = jax.random.normal(ks[0], (s, t), jnp.float32)
        row["top_k_alone_ms"] = _time(jax.jit(
            lambda sc: ss.select(sc, jnp.arange(t)[None] <= pos_d[:, None],
                                 TOPK)), scores)
        idx, valid = jax.jit(select)(i_pool, q_idx, w_idx)
        row["gather_attend_ms"] = _time(jax.jit(attend), k_pool, v_pool, q,
                                        idx, valid)
        row["whole_ms"] = _time(jax.jit(whole), k_pool, v_pool, i_pool, q,
                                q_idx, w_idx)
        say(**row)
        del k_pool, v_pool, i_pool

    if "dma" in parts:
        # the cell's pool: 24,576 blocks of 16 rows of 1 KiB; 16 slots read
        # 2,048 rows each at random (seeded weights select near-uniformly)
        n_rows, words = 24576 * bs, NKV * HD // 2
        ks = jax.random.split(jax.random.PRNGKey(21), 3)
        k32, v32 = (jax.random.bits(kk, (n_rows, 1, words), jnp.uint32)
                    for kk in ks[:2])
        at = jax.random.randint(ks[2], (s, TOPK), 0, n_rows, jnp.int32)
        gather = jax.jit(lambda pools, at: tuple(p[at.reshape(-1)]
                                                 for p in pools))
        for pools in ((k32,), (k32, v32)):
            row = {"what": "fetch_rows", "slots": s, "rows_a_slot": TOPK,
                   "pools": len(pools), "row_bytes": 4 * words}
            want = gather(pools, at)
            per = 1e6 / (s * TOPK * len(pools))     # ms a call -> ns a row
            row["xla_gather_ns_a_row"] = _time(gather, pools, at) * per
            for inflight in (4, 8, 16, 32):
                fn = jax.jit(lambda pools, at, n=inflight:
                             fetch_rows(pools, at, n))
                got = fn(pools, at)
                assert all(bool((g == w).all()) for g, w in zip(got, want))
                row[f"dma_{inflight}_in_flight_ns_a_row"] = \
                    _time(fn, pools, at) * per
            say(**row)
        del k32, v32

    rows = ss.QUERY_TILE
    for extent in (8192, 16384, 28672) if "tile" in parts else ():
        ks = jax.random.split(jax.random.PRNGKey(13), 6)
        k = jax.random.normal(ks[0], (1, NKV, extent, HD), bf)
        v = jax.random.normal(ks[1], (1, NKV, extent, HD), bf)
        keys = jax.random.normal(ks[2], (1, extent, ID), bf)
        q = jax.random.normal(ks[3], (1, NH, rows, HD), bf)
        q_idx = jax.random.normal(ks[4], (1, rows, IH, ID), bf)
        w_idx = jax.random.normal(ks[5], (1, rows, IH), bf) * 0.02
        visible = jnp.arange(extent)[None, :] \
            <= (extent - rows + jnp.arange(rows))[:, None]
        kt, vt = (a[0].transpose(1, 0, 2).reshape(extent, NKV * HD)
                  for a in (k, v))

        def score(keys, q_idx, w_idx):
            return ss.index_scores(q_idx, w_idx, keys)

        def masked(q, k, v, scores):
            return ss.gqa_masked_attention(
                q, k, v, ss.select_mask(scores, visible, TOPK))

        def mask_alone(scores):
            return ss.select_mask(scores, visible, TOPK)

        def gathered(q, kt, vt, scores):
            idx, valid = ss.select(scores, visible, TOPK)
            return ss.gqa_selected_attention(q[0].transpose(1, 0, 2),
                                             kt[idx[0]], vt[idx[0]], valid[0])

        scores = jax.jit(score)(keys, q_idx, w_idx)
        say(what="prefill_tile", rows=rows, extent=extent,
            scoring_ms=_time(jax.jit(score), keys, q_idx, w_idx),
            select_mask_ms=_time(jax.jit(mask_alone), scores),
            masked_form_ms=_time(jax.jit(masked), q, k, v, scores),
            gather_form_ms=_time(jax.jit(gathered), q, kt, vt, scores))


if __name__ == "__main__":
    main()

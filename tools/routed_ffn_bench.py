#!/usr/bin/env python
"""``models.moe.routed_ffn`` alone on the chip, both its forms: every held
expert on every row, and ``ops.grouped_ffn.grouped_expert_ffn`` (the pairs
sorted by expert, each touched expert computed on its own rows).  One layer's
expert bank at a model's widths, ``--rows`` rows a call; ``--held`` of the
router's ``--experts`` in the bank (default: all), as a chip that shares a
layer with others holds them; 8 chained calls a program (each call's output is
the next one's input, so nothing is hoisted), the median of 7.

    chiprun -- python tools/routed_ffn_bench.py --experts 128 --k 8 \
        --width 768 --rows 128,256,512,2048            # SDAR-30B-A3B
    chiprun -- python tools/routed_ffn_bench.py --experts 64 --k 4 \
        --width 1536 --score sigmoid --rows 128,256,512,2048   # LFM2-24B-A2B
    chiprun -- python tools/routed_ffn_bench.py --router-experts 512 \
        --held 128 --k 10 --width 512 --rows 128,256,384,512  # Qwen3-Next, 1 of 4
    chiprun -- python tools/routed_ffn_bench.py --router-experts 256 \
        --held 16 --k 8 --hidden 6144 --width 2048 --score sigmoid \
        --rows 8192,16384,32768 --tiles 128,512x256,256@5376  # GLM-5, 1 of 16

    chiprun -- python tools/routed_ffn_bench.py --train --parts \
        --router-experts 256 --held 16 --k 8 --hidden 2048 --width 768 \
        --score sigmoid --rows 16384          # JoyAI-LLM-Flash trained, 1 of 16
    chiprun -- python tools/routed_ffn_bench.py --kind relu2 \
        --router-experts 512 --held 128 --k 22 --hidden 4096 --latent 1024 \
        --width 2688 --score sigmoid --rows 128,512  # Nemotron 3 Super, 1 of 4

``--kind relu2`` makes the experts two-matrix squared-ReLU products (no gate);
``--latent`` is the width the experts multiply in where that is not the
router's (``routed_ffn``'s ``rows``: the rows are projected down once outside
the timed call and the result stays in the latent width).

Prints one JSON line a size: milliseconds a call of each form, the
whole layer with its routing, the kernel at each ``--tiles`` entry (a row
tile, ``rows x width tile``, or either ``@`` a window of sorted pairs), which form ``moe.expert_product`` picks there
with the tiles and the window the shapes give, and what the one form's
operations (every held expert on every row) and the bank's bytes come to.
With ``--parts`` a second line a size.  Where the call's pairs fit one window
(``ops.grouped_ffn.rows_form`` says ``"resident"``: a served step's rows) the
pieces of that call jitted alone: the listing by a sort of the ``N x k`` keys
(the program's) beside the same pairs listed by count (``_held_pairs``), the
visits, the kernel over a finished listing, what is left in XLA (the rows'
float32 copy), and the whole call under either listing.  Else the milliseconds
of each piece of ONE
window of the windowed form, jitted alone at the window's shapes (the listing,
the rows in, each kernel, the combine ``grouped_expert_ffn_rows`` beside XLA's
scatter-add of the same rows, the combine weights' gradient), the router's
pieces around the layer, and the whole call forward and forward + backward;
``dispatch_floor_ms`` is what an empty program reads on this host, under which
a piece cannot be told from nothing.
The tables that set ``ops.grouped_ffn.GROUPED_MIN_ROWS`` (PERF.md, PRs 31 and
48), ``ROW_TILE`` (PR 31), ``ROW_TILE_WALKED`` (PR 33) and ``_ONE_WINDOW_BYTES``
(PR 46) are this tool's kind of output.
"""
import argparse
import json
import os
import statistics
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", "--router-experts", type=int, default=128,
                    help="experts the router chooses among")
    ap.add_argument("--held", type=int, default=None,
                    help="experts in the bank: the router's first so many "
                         "(default: all)")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--rows", default="128,256,512,2048")
    ap.add_argument("--tiles", default=str(grouped_ffn.ROW_TILE))
    ap.add_argument("--score", default="softmax")
    ap.add_argument("--kind", default="swiglu", choices=("swiglu", "relu2"),
                    help="the expert: three matrices, or relu(x W1)^2 W2")
    ap.add_argument("--latent", type=int, default=None,
                    help="the width the experts multiply in, where it is "
                         "not the router's --hidden (a latent expert layer)")
    ap.add_argument("--train", action="store_true",
                    help="time forward AND backward of each form (the "
                         "gradient in the rows, the router and the bank), "
                         "one call a program, not the forward chained")
    ap.add_argument("--parts", action="store_true",
                    help="also time each piece of one window of the "
                         "windowed form alone, and the router's pieces")
    args = ap.parse_args()
    e, k, i = args.experts, args.k, args.width
    wide, h = args.hidden, args.latent or args.hidden
    relu2, mats = args.kind == "relu2", 2 if args.kind == "relu2" else 3
    if (relu2 or h != wide) and (args.train or args.parts):
        raise SystemExit("--train and --parts time the swiglu kernels at the "
                         "router's width")
    held = args.held or e
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    rw = jax.random.normal(keys[0], (e, wide), bf) * 0.02
    wg = None if relu2 else jax.random.normal(keys[1], (held, h, i), bf) * 0.02
    wu = jax.random.normal(keys[2], (held, h, i), bf) * 0.02
    wd = jax.random.normal(keys[3], (held, i, h), bf) * 0.02
    # a latent layer routes on the wide rows and computes on their
    # projection: the wide rows (a row of its own each, so that the rows
    # spread over the experts) ride along, the latent ones chain
    most = max(int(r) for r in args.rows.split(","))
    routed = jax.random.normal(keys[5], (most, wide), bf) \
        if h != wide else None

    # the bank rides as an argument: closed over, its 1.2 GB would be
    # constants of every program, and each compile takes minutes
    bank = (rw, wg, wu, wd)

    def form(name):
        def fn(x, rw, wg, wu, wd):
            # read where routed_ffn is traced: once a program
            with mock.patch.object(moe, "expert_product",
                                   lambda *a: name):
                if routed is None:
                    return moe.routed_ffn(x, rw, wg, wu, wd, k,
                                          score=args.score,
                                          experts_held=(0, held),
                                          kind=args.kind)[0]
                wide_rows = routed[:x.shape[0]] + x[:, :1]
                return moe.routed_ffn(wide_rows, rw, wg, wu, wd, k,
                                      score=args.score,
                                      experts_held=(0, held),
                                      kind=args.kind, rows=x)[0]
        return fn

    def at_tile(tm, wt, window):
        def fn(x, rw, wg, wu, wd):
            r = x if routed is None else routed[:x.shape[0]] + x[:, :1]
            idx, w = moe.route(r, rw, k, args.score)
            return grouped_ffn.grouped_expert_ffn(
                x, idx, w, wg, wu, wd, row_tile=tm, width_tile=wt,
                window=window, kind=args.kind)
        return fn

    def chained(fn):
        if args.train:
            # one layer's forward and backward: XLA's gradient of the
            # every-expert form, the kernels' custom_vjp of the other
            def loss(x, bank):
                return fn(x, *bank).astype(jnp.float32).sum()

            grad = jax.grad(loss, argnums=(0, 1))

            def run(x, bank):
                for _ in range(8):
                    dx, dbank = grad(x, bank)
                    x = (x + dx * 1e-3).astype(bf)
                    bank = tuple((a + d * 1e-3).astype(a.dtype)
                                 for a, d in zip(bank, dbank))
                return x
            return jax.jit(run)

        def run(x, bank):
            for _ in range(8):
                x = (x + fn(x, *bank)).astype(bf)
            return x
        return jax.jit(run)

    columns = [("every_expert", form("every_expert")),
               ("grouped_kernel", form("grouped_kernel"))]
    row_tile, width_tile = grouped_ffn.tiles(h, i) or (None, None)
    for entry in args.tiles.split(","):
        entry, _, window = entry.partition("@")
        tm, _, wt = entry.partition("x")
        tm, wt = int(tm), int(wt or width_tile or i)
        if window:
            columns.append((f"tile_{tm}x{wt}@{window}",
                            at_tile(tm, wt, int(window))))
        elif (tm, wt) != (row_tile, width_tile):
            columns.append((f"tile_{tm}x{wt}", at_tile(tm, wt, None)))
    dev = jax.devices()[0]
    for n in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(keys[4], (n, h), bf)
        row = {"train": bool(args.train), "kind": args.kind,
               "rows": n, "experts": e, "held": held, "k": k, "hidden": h,
               "router_hidden": wide,
               "width": i, "device": dev.device_kind,
               "rule_picks": moe.expert_product(n, k, held, h, i, bf),
               "row_tile": row_tile, "width_tile": width_tile,
               "window_pairs": row_tile
               and grouped_ffn.window_pairs(n, k, h, row_tile),
               "every_expert_gflop": 2 * mats * n * held * h * i / 1e9,
               "routed_gflop": 2 * mats * n * k * h * i * held / e / 1e9,
               "bank_gb": mats * held * h * i * 2 / 1e9}
        outs = {}
        for name, fn in columns:
            prog = chained(fn)
            outs[name] = jax.block_until_ready(prog(x, bank))
            times = []
            for _ in range(7):
                t = time.perf_counter()
                jax.block_until_ready(prog(x, bank))
                times.append((time.perf_counter() - t) / 8 * 1e3)
            row[name + "_ms"] = round(statistics.median(times), 4)
        a = outs["every_expert"].astype(jnp.float32)
        b = outs["grouped_kernel"].astype(jnp.float32)
        row["forms_differ_rel"] = float(jnp.abs(a - b).max()
                                        / jnp.abs(a).max())
        print(json.dumps(row), flush=True)
        if args.parts:
            print(json.dumps(parts(args, x, bank, held)), flush=True)


def parts(args, x, bank, held):
    """One window's pieces alone (see the module text)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn as g

    rw, wg, wu, wd = bank
    n, h = x.shape
    k = args.k
    tm, wt = g.tiles(h, wg.shape[2])
    win = g.window_pairs(n, k, h, tm)
    tt, c = g.token_rows(n, h), min(g._CHUNK, tm)

    def ms(fn, *a, donate=None):
        fn = jax.jit(fn, donate_argnums=() if donate is None else donate)
        out = jax.block_until_ready(fn(*a))
        times = []
        for _ in range(7):
            t = time.perf_counter()
            for _ in range(4):
                if donate is not None:
                    a = a[:donate] + (out,) + a[donate + 1:]
                out = fn(*a)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t) / 4 * 1e3)
        return round(statistics.median(times), 4), out

    resident = g.rows_form(n, k, h, wg.shape[2]) == "resident"
    row = {"parts_of": "the resident call" if resident else "one window",
           "device": jax.devices()[0].device_kind,
           "rows": n, "window_pairs": win,
           "row_tile": tm, "token_tile": tt, "chunk": c}
    row["dispatch_floor_ms"], _ = ms(lambda a: a + 1, jnp.zeros((8,)))
    if resident:
        return parts_resident(args, row, ms, x, bank, held, tm, wt)
    # the router around the layer, as ``routed_ffn`` runs it
    row["route_ms"], (idx, w) = ms(
        lambda x, rw: moe.route(x, rw, k, args.score), x, rw)
    row["route_fwd_bwd_ms"], _ = ms(jax.grad(
        lambda x, rw: moe.route(x, rw, k, args.score)[1].sum(),
        argnums=(0, 1)), x, rw)
    e = rw.shape[0]
    row["expert_counts_ms"], _ = ms(
        lambda idx: jnp.zeros((e,), jnp.int32).at[idx].add(1), idx)

    def listing(idx):
        pairs, window, runs = g._held_pairs(idx, held, None, tt)
        return (pairs,) + window(0, win) + runs(0, win, c)

    row["listing_ms"], (pairs, rows, key_w, slot, *walk) = ms(listing, idx)
    row["pairs_held"] = int(pairs)
    row["weights_in_ms"], ws = ms(
        lambda w, rows, slot: jnp.where(slot, w[rows], 0.0).sum(1),
        w.astype(jnp.float32), rows, slot)
    row["rows_in_ms"], xw = ms(lambda x, rows: x[rows], x, rows)
    row["fwd_kernel_with_rows_in_ms"], out = ms(
        lambda x, rows, key, ws, *b: g._window(x, rows, key, ws, b, tm, wt,
                                               False),
        x, rows, key_w, ws, wg, wu, wd)
    total = jnp.zeros((n, h), jnp.float32)
    row["combine_kernel_ms"], total = ms(
        lambda out, rows, total, *walk: g._add_rows(
            total, out, rows, walk, tt, c, False),
        out, rows, total, *walk, donate=2)
    row["combine_xla_scatter_add_ms"], total = ms(
        lambda out, rows, total, key: total.at[rows].add(
            jnp.where((key < held)[:, None], out, 0.0)),
        out, rows, total, key_w, donate=2)
    del total
    if wt == wg.shape[2]:
        accs = tuple(jnp.zeros(a.shape, jnp.float32) for a in (wg, wu, wd))
        row["bwd_kernels_ms"], _ = ms(
            lambda xw, dyw, key, ws, accs, *b: g._window_bwd(
                xw, dyw, key, ws, b, accs, tm, False)[2],
            xw, xw, key_w, ws, accs, wg, wu, wd, donate=4)
        row["weights_grad_scatter_add_ms"], _ = ms(
            lambda rows, slot, d: jnp.zeros((n, k), jnp.float32).at[rows].add(
                jnp.where(slot, d[:, None], 0.0)), rows, slot, ws)

    whole = g.grouped_expert_ffn
    row["whole_fwd_ms"], _ = ms(whole, x, idx, w, wg, wu, wd)
    if wt == wg.shape[2]:
        row["whole_fwd_bwd_ms"], _ = ms(jax.grad(
            lambda x, w, *b: whole(x, idx, w, *b).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4)), x, w, wg, wu, wd)
    return row


def parts_resident(args, row, ms, x, bank, held, tm, wt):
    """The pieces of a call whose pairs fit one window (see the module
    text)."""
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn as g

    rw, wg, wu, wd = bank
    n, k = x.shape[0], args.k
    mp = -(-n * k // tm) * tm
    row["route_ms"], (idx, w) = ms(
        lambda x, rw: moe.route(x, rw, k, args.score), x, rw)

    def by_sort(idx, w):
        return g._sorted_pairs(idx, w, held, None, tm)

    def by_count(idx, w):
        _, window, _ = g._held_pairs(idx, held, None, n)
        rows, key, slot = window(0, mp)
        return rows, key, jnp.where(slot, w.astype(jnp.float32)[rows],
                                    0.0).sum(axis=1)

    row["listing_sort_ms"], (rows, key, ws) = ms(by_sort, idx, w)
    row["listing_count_ms"], counted = ms(by_count, idx, w)
    row["pairs_held"] = int((key < held).sum())
    row["listings_agree"] = all(
        bool((a[:row["pairs_held"]] == b[:row["pairs_held"]]).all())
        for a, b in zip((rows, key, ws), counted))
    row["visits_ms"], visits = ms(
        lambda key: g._own_visits(key, held, tm), key)
    row["visits"] = int(visits[3][0])
    row["rows_to_float32_ms"], _ = ms(lambda x: x.astype(jnp.float32), x)

    def kernel(x, rows, key, ws, *b):
        return g._resident_visits(x, rows, key, ws, b, tm, wt, False)

    row["kernel_with_visits_ms"], _ = ms(kernel, x, rows, key, ws, wg, wu, wd)
    row["whole_fwd_ms"], _ = ms(g.grouped_expert_ffn, x, idx, w, wg, wu, wd)
    row["whole_fwd_listed_by_count_ms"], _ = ms(
        lambda x, idx, w, *b: kernel(x, *by_count(idx, w), *b),
        x, idx, w, wg, wu, wd)
    return row


if __name__ == "__main__":
    main()

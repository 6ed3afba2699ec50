#!/usr/bin/env python
"""``models.moe.routed_ffn`` alone on the chip, both its forms: every held
expert on every row, and ``ops.grouped_ffn.grouped_expert_ffn`` (the pairs
sorted by expert, each touched expert streamed once).  One layer's expert bank
at a model's widths, ``--rows`` rows a call; 8 chained calls a program (each
call's output is the next one's input, so nothing is hoisted), the median of 7.

    chiprun -- python tools/routed_ffn_bench.py --experts 128 --k 8 \
        --width 768 --rows 128,256,512,2048            # SDAR-30B-A3B
    chiprun -- python tools/routed_ffn_bench.py --experts 64 --k 4 \
        --width 1536 --score sigmoid --rows 128,256,512,2048   # LFM2-24B-A2B

Prints one JSON line a size: milliseconds a call of each form, the
whole layer with its routing, the kernel at each ``--tiles`` row tile, which
form ``moe.expert_product`` picks there, and what the one form's operations
(every held expert on every row) and the bank's bytes come to.  The table that
set ``ops.grouped_ffn.GROUPED_MIN_ROWS`` and ``ROW_TILE`` (PERF.md, PR 31) is
this tool's output.
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
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--rows", default="128,256,512,2048")
    ap.add_argument("--tiles", default=str(grouped_ffn.ROW_TILE))
    ap.add_argument("--score", default="softmax")
    args = ap.parse_args()
    e, k, h, i = args.experts, args.k, args.hidden, args.width
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    rw = jax.random.normal(keys[0], (e, h), bf) * 0.02
    wg = jax.random.normal(keys[1], (e, h, i), bf) * 0.02
    wu = jax.random.normal(keys[2], (e, h, i), bf) * 0.02
    wd = jax.random.normal(keys[3], (e, i, h), bf) * 0.02

    # the bank rides as an argument: closed over, its 1.2 GB would be
    # constants of every program, and each compile takes minutes
    bank = (rw, wg, wu, wd)

    def form(name):
        def fn(x, rw, wg, wu, wd):
            # read where routed_ffn is traced: once a program
            with mock.patch.object(moe, "expert_product",
                                   lambda *a: name):
                return moe.routed_ffn(x, rw, wg, wu, wd, k,
                                      score=args.score)[0]
        return fn

    def at_tile(tm):
        def fn(x, rw, wg, wu, wd):
            idx, w = moe.route(x, rw, k, args.score)
            return grouped_ffn.grouped_expert_ffn(x, idx, w, wg, wu, wd,
                                                  row_tile=tm)
        return fn

    def chained(fn):
        def run(x, bank):
            for _ in range(8):
                x = (x + fn(x, *bank)).astype(bf)
            return x
        return jax.jit(run)

    columns = [("every_expert", form("every_expert")),
               ("grouped_kernel", form("grouped_kernel"))]
    columns += [(f"tile_{tm}", at_tile(int(tm)))
                for tm in args.tiles.split(",")
                if int(tm) != grouped_ffn.ROW_TILE]
    dev = jax.devices()[0]
    for n in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(keys[4], (n, h), bf)
        row = {"rows": n, "experts": e, "k": k, "width": i,
               "device": dev.device_kind,
               "rule_picks": moe.expert_product(n, k, e, h, i, bf),
               "every_expert_gflop": 2 * 3 * n * e * h * i / 1e9,
               "routed_gflop": 2 * 3 * n * k * h * i / 1e9,
               "bank_gb": 3 * e * h * i * 2 / 1e9}
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


if __name__ == "__main__":
    main()

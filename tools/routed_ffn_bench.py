#!/usr/bin/env python
"""``models.moe.routed_ffn`` alone on the chip, against a grouped form: rows
sorted by expert and ``jax.lax.ragged_dot``.  One layer's expert bank at a
model's widths, ``--rows`` rows a call; 8 chained calls a program (each call's
output is the next one's input, so nothing is hoisted), the median of 7.

    chiprun -- python tools/routed_ffn_bench.py --experts 128 --k 8 \
        --width 768 --rows 128,512,2048

Prints one JSON line a size: milliseconds a call of both forms, and what the
one form's operations (every held expert on every row) and the layer's bytes
come to.  The grouped form is this file's alone: ``routed_ffn`` has one form
until a size shows the other ahead (PERF.md section 7).
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe

    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=768)
    ap.add_argument("--rows", default="128,512,2048")
    ap.add_argument("--score", default="softmax")
    args = ap.parse_args()
    e, k, h, i = args.experts, args.k, args.hidden, args.width
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    bf = jnp.bfloat16
    rw = jax.random.normal(keys[0], (e, h), bf) * 0.02
    wg = jax.random.normal(keys[1], (e, h, i), bf) * 0.02
    wu = jax.random.normal(keys[2], (e, h, i), bf) * 0.02
    wd = jax.random.normal(keys[3], (e, i, h), bf) * 0.02

    def dense(x):
        return moe.routed_ffn(x, rw, wg, wu, wd, k, score=args.score)[0]

    def grouped(x):
        n = x.shape[0]
        idx, w = moe.route(x, rw, k, args.score)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat)
        rows = x[order // k]
        sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        g = jax.lax.ragged_dot(rows, wg, sizes)
        u = jax.lax.ragged_dot(rows, wu, sizes)
        y = jax.lax.ragged_dot(g * jax.nn.sigmoid(g) * u, wd, sizes)
        y = y * w.reshape(-1)[order].astype(y.dtype)[:, None]
        return jnp.zeros((n, h), y.dtype).at[order // k].add(y)

    def chained(fn):
        def run(x):
            for _ in range(8):
                x = (x + fn(x)).astype(bf)
            return x
        return jax.jit(run)

    dev = jax.devices()[0]
    for n in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(keys[4], (n, h), bf)
        row = {"rows": n, "experts": e, "k": k, "width": i,
               "device": dev.device_kind,
               "dense_gflop": 2 * 3 * n * e * h * i / 1e9,
               "routed_gflop": 2 * 3 * n * k * h * i / 1e9,
               "bank_gb": 3 * e * h * i * 2 / 1e9}
        outs = {}
        for name, fn in (("dense", dense), ("grouped", grouped)):
            prog = chained(fn)
            outs[name] = jax.block_until_ready(prog(x))
            times = []
            for _ in range(7):
                t = time.perf_counter()
                jax.block_until_ready(prog(x))
                times.append((time.perf_counter() - t) / 8 * 1e3)
            row[name + "_ms"] = round(statistics.median(times), 4)
        a = outs["dense"].astype(jnp.float32)
        b = outs["grouped"].astype(jnp.float32)
        row["forms_differ_rel"] = float(jnp.abs(a - b).max()
                                        / jnp.abs(a).max())
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

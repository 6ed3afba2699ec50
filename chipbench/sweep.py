#!/usr/bin/env python3
"""Find an open-loop cell's knee once: one process, one set-up, one window per
rate.  The knee is the highest rate of the sweep at which the backlog when the
last request is due (requests sent and not finished) is no larger than
``num_slots``; the cell's mix then fixes ``rate_per_s`` at 0.8 of it.

    python chipbench/sweep.py --workload mistral7b.doc_prefill --seed 7 \
        --seconds 30 --rates 1.0,1.5,2.0,2.5,3.0,3.5
"""
from __future__ import annotations

import argparse
import json

import run as harness
import reduce_helpers as rh
import traffic


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    ctx = harness.set_up(args.workload, args.seed, args.seconds)
    cell, mix, config = ctx["cell"], ctx["mix"], ctx["config"]
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        work = traffic.make_requests(dict(mix, rate_per_s=rate), args.seed + i,
                                     config["vocab_size"], args.seconds)
        obs = cell.window(args.seconds, work)
        last_due = max(r["due"] for r in obs["requests"])
        backlog = sum(1 for r in obs["requests"]
                      if r["t_done"] is None or r["t_done"] > last_due)
        row = {"rate_per_s": rate, "requests": len(obs["requests"]),
               "failed": obs["failed"], "backlog_at_last_due": backlog,
               "num_slots": obs["num_slots"],
               "drain_after_window_s": obs["drained_s"] - args.seconds,
               "ttft_p50_ms": rh.percentile([rh.ttft_ms(r, obs) for r in obs["requests"]], 50),
               "ttft_p90_ms": rh.percentile([rh.ttft_ms(r, obs) for r in obs["requests"]], 90),
               "tpot_p90_ms": rh.percentile([t for t in map(rh.tpot_ms, obs["requests"])
                                             if t is not None], 90)}
        rows.append(row)
        print("sweep:", json.dumps(row), flush=True)
    ok = [r["rate_per_s"] for r in rows if r["backlog_at_last_due"] <= r["num_slots"]]
    print("knee:", json.dumps({"knee_per_s": max(ok) if ok else None,
                               "rate_at_0.8": 0.8 * max(ok) if ok else None}))
    cell.end_window()
    cell.finish()


if __name__ == "__main__":
    main()

"""Median host time of one train dispatch, entry to the jitted call's return (t0 to t_disp1 of the lane log's train.dispatch records): what the host pays to launch K optimizer steps."""
import statistics

import lane_spans

NAME = "train_dispatch_host_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "trainer"
MOVES = "train_tok_per_s_chip"


def read(obs):
    t0 = obs["t0_abs"]
    host = [(r["t_disp1"] - r["t0"]) * 1e3
            for r in lane_spans.records(obs, "train.dispatch") if r["t0"] >= t0]
    return statistics.median(host) if host else None

"""Model FLOP/s utilisation of a step with routed experts: tokens per second per chip of this run times the operations a token needs forward and backward (flops_bytes/joyai_flash_train.py: the causal half of attention, the (row, expert) pairs that fell on the HELD experts as the train.dispatch records count them (pairs_held; the serving reader experts_touched_share divides touched experts by the router's width, which this does not), no recomputation) over the chip's bf16 peak.  Nothing to read where the records carry no pairs_held."""
import statistics

import lane_spans
from flops_bytes import joyai_flash_train as cost

NAME = "train_mfu.moe"
UNIT = "%"
SOURCE = "host_clock"
LAYER = "device"
MOVES = "train_tok_per_s_chip"


def pairs_per_step(obs):
    steps = [p for r in lane_spans.records(obs, "train.dispatch")
             for p in r.get("pairs_held", ())]
    return statistics.fmean(steps) if steps else None


def read(obs):
    pairs = pairs_per_step(obs)
    if obs["peaks"] is None or pairs is None:
        return None
    cfg = obs["config"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg["num_nextn_predict_layers"]
    per_token = pairs / (obs["rows"] * obs["seq"]) / layers
    rate = obs["tokens"] / obs["window_s"] / obs["chips"]
    return 100.0 * rate * cost.flops_per_token(cfg, obs["seq"], per_token) \
        / obs["peaks"]["bf16_flops_per_s"]

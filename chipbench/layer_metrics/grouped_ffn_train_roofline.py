"""The grouped expert kernels of a training step (the forward and the backward's grouped_expert_ffn_dx and grouped_expert_ffn_dw, found by their names in the trace's op_seconds) on chip 0: the larger of the held pairs' operation time and the byte time of the touched banks once a kernel and the pairs' rows (flops_bytes/grouped_ffn_train.py; the pairs are the train.dispatch records' pairs_held), over the optimizer steps of the traced window, over the kernels' device time."""
import statistics

import lane_spans
from flops_bytes import grouped_ffn_train as grouped

NAME = "grouped_ffn_train_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tok_per_s_chip"

GROUPED = "grouped_expert_ffn"


def read(obs):
    if obs["peaks"] is None or obs.get("trace") is None:
        return None
    steps_pairs = [p for r in lane_spans.records(obs, "train.dispatch")
                   for p in r.get("pairs_held", ())]
    pairs = statistics.fmean(steps_pairs) if steps_pairs else None
    chip = obs["trace"]["chips"][0]
    spent = sum(s for n, s in chip["op_seconds"].items() if GROUPED in n)
    if pairs is None or spent <= 0:
        return None
    cfg, pk, t = obs["config"], obs["peaks"], obs["trace"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg["num_nextn_predict_layers"]
    steps = obs["optimizer_steps"] / obs["window_s"] * t["window_s"]
    remat = obs.get("step_facts", {}).get("remat", "none") != "none"
    flops, nbytes = grouped.needs(cfg, pairs, layers, recompute=remat)
    least = steps * max(flops / pk["bf16_flops_per_s"],
                        nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent

"""Mean over decode ticks of the experts that received at least one row, over the expert layers, as a share of all of them (the tick records' experts_touched over expert layers x experts): what part of the expert weights a tick has to read.  Nothing to read on a program whose tick records carry no experts_touched."""
import statistics

import lane_spans

NAME = "experts_touched_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    cfg = obs["config"]
    if "num_experts" not in cfg:
        return None
    every = (cfg["num_hidden_layers"] - cfg["num_dense_layers"]) * cfg["num_experts"]
    touched = [t["experts_touched"]
               for t in lane_spans.records(obs, "decode.tick")
               if "experts_touched" in t]
    return 100.0 * statistics.fmean(touched) / every if touched else None

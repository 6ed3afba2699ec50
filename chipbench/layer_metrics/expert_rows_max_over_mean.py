"""Median over decode ticks of the most rows any expert of any layer received over the mean rows of the experts that received some (the tick records' expert_rows_max / expert_rows_mean): 1.0 is perfect balance.  Nothing to read on a program whose tick records carry neither."""
import statistics

import lane_spans

NAME = "expert_rows_max_over_mean"
UNIT = "ratio"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    ratios = [t["expert_rows_max"] / t["expert_rows_mean"]
              for t in lane_spans.records(obs, "decode.tick")
              if t.get("expert_rows_mean")]
    return statistics.median(ratios) if ratios else None

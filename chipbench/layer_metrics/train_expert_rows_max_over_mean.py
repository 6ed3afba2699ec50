"""Median over the window's optimizer steps of the most rows a held expert of any layer received over the mean rows of the held experts (the train.dispatch records' expert_rows_max / expert_rows_mean, a number a step): 1.0 is perfect balance.  Nothing to read on a program whose records carry neither."""
import statistics

import lane_spans

NAME = "train_expert_rows_max_over_mean"
UNIT = "ratio"
SOURCE = "program_counter"
LAYER = "trainer"
MOVES = "train_tok_per_s_chip"


def read(obs):
    ratios = [most / mean
              for r in lane_spans.records(obs, "train.dispatch")
              for most, mean in zip(r.get("expert_rows_max", ()),
                                    r.get("expert_rows_mean", ()))
              if mean]
    return statistics.median(ratios) if ratios else None

"""Share of the window's decode ticks dispatched while a prefill batch's forward was on the device and its first tokens not yet on the host (a non-empty behind in the tick's record)."""
import turn_spans

NAME = "ticks_behind_prefill_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    split = turn_spans.ticks_by_behind(obs)
    if split is None:
        return None
    return 100.0 * len(split[0]) / (len(split[0]) + len(split[1]))

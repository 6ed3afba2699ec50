"""Percent of the window that both lanes' stalls with the cause own lasted (the program's tracing.stalls: a turn whose host part passes the median of its kind by more than 20 ms; own: the lane thread's own CPU seconds cover at least half of it: the lane computed); 0.0 in a window that held none."""
import stall_spans

NAME = "stall_share.own"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return stall_spans.stall_share(obs, ("own",))

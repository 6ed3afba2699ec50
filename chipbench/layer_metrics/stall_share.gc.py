"""Percent of the window that both lanes' stalls with the cause gc lasted (the program's tracing.stalls: a turn whose host part passes the median of its kind by more than 20 ms; gc: the garbage collector's pauses overlap at least half of it); 0.0 in a window that held none."""
import stall_spans

NAME = "stall_share.gc"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return stall_spans.stall_share(obs, ("gc",))

"""Percent of the window that both lanes' stalls with the cause offcpu lasted (the program's tracing.stalls: a turn whose host part passes the median of its kind by more than 20 ms; offcpu: no collection ran and the lane did not compute: another thread of the process held the interpreter's lock or a lock of the runtime, or the machine ran nothing of the process); 0.0 in a window that held none."""
import stall_spans

NAME = "stall_share.offcpu"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return stall_spans.stall_share(obs, ("offcpu",))

"""Percent of the window that both lanes' stalls lasted, every cause summed (see stall_share.gc), where the prefill lane contends for the device and one stall of 99 ms is itl_p99_ms by itself."""
import stall_spans

NAME = "stall_share.doc"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "tpot_p90_ms"


def read(obs):
    return stall_spans.stall_share(obs)

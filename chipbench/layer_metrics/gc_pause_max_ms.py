"""The longest pause of the garbage collector that overlaps the window (the lane log's gc.pause records, written for a collection of 1 ms or longer by whichever thread set it off); 0.0 where none reached 1 ms."""
import stall_spans

NAME = "gc_pause_max_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return stall_spans.gc_pause_max_ms(obs)

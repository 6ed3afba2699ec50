"""Mean over the window's decode turns that did not stall of the host's part of a turn (tick_host_ms: the period less the wait for the device's tokens) less the lane thread's CPU seconds over the same stretches (means of both: the host's CPU clock ticks at 10 ms): the time in which the lane neither waited for the device nor ran, from the c_* stamps of the program's lane log."""
import stall_spans

NAME = "tick_offcpu_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return stall_spans.tick_offcpu_ms(obs)

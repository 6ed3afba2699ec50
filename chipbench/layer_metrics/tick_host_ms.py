"""Median over decode ticks of the tick's period (top of the lane's loop to the next) less the wait for the device's tokens: the host's part of a tick, from the program's lane log."""
import lane_spans

NAME = "tick_host_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return lane_spans.tick_host_ms(obs)

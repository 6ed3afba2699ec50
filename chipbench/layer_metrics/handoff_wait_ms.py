"""Median over the window's turns (the ramp's included) of t_adopt - t_first: a committed prefill lying in the decode lane's hand-off queue until the lane's next turn."""
import turn_spans

NAME = "handoff_wait_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return turn_spans.median_ms(turn_spans.turns(obs), "t_first", "t_adopt")

"""Median device time of the decode step program in the traced window."""
import reduce_helpers as rh

NAME = "decode_step_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    return rh.median_module_ms(obs, 'step')

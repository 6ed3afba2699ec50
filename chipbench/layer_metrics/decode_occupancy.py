"""Active slots per decode tick over num_slots: decode tokens emitted in the window over ticks (engine.steps) times slots."""
import reduce_helpers as rh

NAME = "decode_occupancy"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    load = rh.decode_tick_load(obs)
    return None if load is None else 100.0 * load[0] / obs["num_slots"]

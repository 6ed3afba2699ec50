"""Median over the window's turns of a slot, from its release to the first token of the request admitted into it: t_tok of the tick that adopted the hand-off less t_free (the leaving request's t_done), over the lane log's slot.turn records of slots released inside the window.  A vacant slot yields nothing, so this is what decode_occupancy's missing share is made of."""
import turn_spans

NAME = "slot_turn_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return turn_spans.median_ms(turn_spans.turns(obs, released=True), "t_free", "t_tok")

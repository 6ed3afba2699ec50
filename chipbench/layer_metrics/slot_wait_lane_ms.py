"""Median over the same turns as slot_turn_ms of t_start - t_free: a freed slot waiting for the prefill lane to take a request for it (the lane busy with another admission, or in its poll)."""
import turn_spans

NAME = "slot_wait_lane_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return turn_spans.median_ms(turn_spans.turns(obs, released=True), "t_free", "t_start")

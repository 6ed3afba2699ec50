"""Rows a block decoder's passes carried over the rows they could: the tick records' rows (active slots x block length) summed over the window's ticks, over ticks x num_slots x block length.  decode_occupancy's formula assumes a token a slot a tick; this one counts slots.  Nothing to read on a program whose tick records carry no block_len."""
import lane_spans

NAME = "block_slot_occupancy"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    ticks = [t for t in lane_spans.records(obs, "decode.tick") if "block_len" in t]
    if not ticks:
        return None
    room = sum(obs["num_slots"] * t["block_len"] for t in ticks)
    return 100.0 * sum(t["rows"] for t in ticks) / room

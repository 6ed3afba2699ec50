"""Passes a block takes, the one that stores its keys and values included: over the decode ticks of the window, the passes of the blocks stored (the tick records' block_passes) over the blocks stored (n_store).  A block of 4 under a schedule of one commit a pass reads 5; a request's first block holds the rest of its prompt and takes fewer.  Nothing to read on a program whose tick records carry no n_store."""
import lane_spans

NAME = "passes_per_block"
UNIT = "passes"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    ticks = [t for t in lane_spans.records(obs, "decode.tick") if "n_store" in t]
    stored = sum(t["n_store"] for t in ticks)
    return sum(t["block_passes"] for t in ticks) / stored if stored else None

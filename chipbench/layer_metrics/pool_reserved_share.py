"""The window's mean, over its decode ticks, of the tokens of block pool that the requests in flight have reserved (the tick records' pool_reserved_tokens: blocks in use x block size) over the pool's tokens (the lane's first record's pool_tokens): near 100% with slots standing free says that the pool, not the slot count, bounds admission.  Nothing to read on a program whose tick records carry no pool_reserved_tokens."""
import statistics

import lane_spans

NAME = "pool_reserved_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    pool = [t["pool_tokens"]
            for t in lane_spans.records(obs, "decode.tick", from_start=True)
            if "pool_tokens" in t]
    held = [t["pool_reserved_tokens"]
            for t in lane_spans.records(obs, "decode.tick")
            if "pool_reserved_tokens" in t]
    if not pool or not held:
        return None
    return 100.0 * statistics.fmean(held) / pool[-1]

"""Over the window's decode ticks of a model that runs its stack several times a token, the bytes of keys and values the step's live tokens hold over every layer and pass (the tick records' kv_bytes: the engine's own price a token, passes counted) over all the bytes the ticks needed (flops_bytes/ouro_decode_step.py: the stack's weights a pass, the head, the keys and values): how much of a tick's traffic is the cache's.  Nothing to read on a program whose tick records carry no kv_bytes."""
import lane_spans
from flops_bytes import ouro_decode_step as decode

NAME = "kv_bytes_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    if "total_ut_steps" not in obs["config"]:
        return None
    kv = needed = 0
    for t in lane_spans.records(obs, "decode.tick"):
        if "kv_bytes" not in t:
            continue
        kv += t["kv_bytes"]
        needed += decode.bytes_needed(obs["config"], t["n_active"],
                                      t["kv_tokens"])
    return 100.0 * kv / needed if needed else None

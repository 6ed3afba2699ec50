"""Over the window's decode ticks of a Nemotron-H model, the bytes of per-slot state the active slots' mixers read and wrote (the tick records' state_bytes) over all the bytes the ticks needed (weights outside the routed experts, the touched held experts, the states, keys and values at true lengths): how much of a tick's traffic is the recurrent state's.  Nothing to read for another family's configuration, or on a program whose tick records carry no state_bytes."""
import lane_spans
from flops_bytes import nemotron_h_decode_step as decode

NAME = "ssm_state_bytes_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    cfg = obs["config"]
    if "mamba_num_heads" not in cfg:
        return None
    state = needed = 0
    for t in lane_spans.records(obs, "decode.tick"):
        if "state_bytes" not in t:
            continue
        state += t["state_bytes"]
        needed += decode.bytes_needed(
            cfg, t["n_active"], t["kv_tokens"],
            t.get("experts_touched_held", t.get("experts_touched", 0)),
            t["state_bytes"])
    return 100.0 * state / needed if needed else None

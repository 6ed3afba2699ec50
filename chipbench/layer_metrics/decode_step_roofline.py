"""The least time a decode tick could take (bytes-bound: weights once and the active slots' keys and values at their true lengths, over the chip's memory bandwidth) over the step program's median device time."""
import reduce_helpers as rh
from flops_bytes import llama_decode_step as decode

NAME = "decode_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None:
        return None
    step_ms = rh.median_module_ms(obs, "step")
    load = rh.decode_tick_load(obs)
    if step_ms is None or load is None:
        return None
    need = decode.bytes_needed(obs["config"], load[0], load[1])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (step_ms * 1e-3)

"""The least time a decode tick of a model with routed experts could take (bytes-bound: every non-expert weight once, the touched experts' weights once, the active slots' keys, values and states, over the chip's memory bandwidth; the load is the tick records' own n_active, kv_tokens and experts_touched) over the step program's median device time.  Nothing to read on a program whose tick records carry no experts_touched."""
import statistics

import lane_spans
import reduce_helpers as rh
from flops_bytes import lfm2_decode_step as decode

NAME = "moe_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None:
        return None
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if "experts_touched" in t]
    if step_ms is None or not ticks:
        return None
    need = statistics.fmean(
        decode.bytes_needed(obs["config"], t["n_active"], t["kv_tokens"],
                            t["experts_touched"]) for t in ticks)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (step_ms * 1e-3)

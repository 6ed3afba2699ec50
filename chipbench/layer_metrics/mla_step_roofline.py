"""The least time a decode tick of a selecting latent model could take (bytes-bound: every weight outside the routed experts once, the touched held experts once, the index keys up to each active slot's position, the selected latent rows, over the chip's memory bandwidth; the load is the tick records' own n_active, kv_visible, kv_selected and experts_touched) over the step program's median device time.  Nothing to read on a program whose tick records carry no kv_selected."""
import statistics

import lane_spans
import reduce_helpers as rh
from flops_bytes import glm_moe_dsa_decode_step as decode

NAME = "mla_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tpot_p90_ms"


def read(obs):
    if obs["peaks"] is None:
        return None
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if "kv_selected" in t]
    if step_ms is None or not ticks:
        return None
    need = statistics.fmean(
        decode.bytes_needed(obs["config"], t["n_active"], t["kv_visible"],
                            t["kv_selected"], t.get("experts_touched", 0))
        for t in ticks)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (step_ms * 1e-3)

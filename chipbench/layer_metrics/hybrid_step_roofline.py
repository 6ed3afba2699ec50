"""The least time a decode tick of a Nemotron-H model could take (the larger of its byte time and its operation time at the chip's peaks: every weight outside the routed experts once, the touched held experts once, each active slot's per-slot states read and written once, keys and values at true lengths; a row's products with the routed experts that lie here; the load is the tick records' own n_active, kv_tokens, experts_touched_held and state_bytes) over the step program's median device time.  Nothing to read for another family's configuration, or on a program whose tick records carry no state_bytes."""
import statistics

import lane_spans
import reduce_helpers as rh
from flops_bytes import nemotron_h_decode_step as decode

NAME = "hybrid_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None or "mamba_num_heads" not in obs["config"]:
        return None
    cfg, pk = obs["config"], obs["peaks"]
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if "state_bytes" in t]
    if step_ms is None or not ticks:
        return None
    least = statistics.fmean(
        max(decode.bytes_needed(cfg, t["n_active"], t["kv_tokens"],
                                t.get("experts_touched_held",
                                      t.get("experts_touched", 0)),
                                t["state_bytes"])
            / pk["hbm_bytes_per_s"],
            decode.flops_needed(cfg, t["n_active"], t["kv_tokens"])
            / pk["bf16_flops_per_s"]) for t in ticks)
    return 100.0 * least / (step_ms * 1e-3)

"""The least time a decode tick of a model that runs its stack several times a token could take (the larger of its byte time and its operation time at the chip's peaks: the stack's weights once a pass, the head once, keys and values at true lengths, a set a pass; flops_bytes/ouro_decode_step.py at the load of the tick records inside the traced stretch: n_active, kv_tokens) over the step program's median device time.  The step has no kernel of its own, so this is the step's share.  Nothing to read on a program whose tick records carry no passes above 1."""
import statistics

import lane_spans
import reduce_helpers as rh
from flops_bytes import ouro_decode_step as decode

NAME = "loop_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None or "total_ut_steps" not in obs["config"]:
        return None
    cfg, pk = obs["config"], obs["peaks"]
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if t.get("passes", 1) > 1]
    traced = [t for t in ticks
              if rh.in_traced_window(obs, t["t_tok"] - obs["t0_abs"])]
    ticks = traced or ticks
    if step_ms is None or not ticks:
        return None
    least = statistics.fmean(
        max(decode.bytes_needed(cfg, t["n_active"], t["kv_tokens"])
            / pk["hbm_bytes_per_s"],
            decode.flops_needed(cfg, t["n_active"], t["kv_tokens"])
            / pk["bf16_flops_per_s"]) for t in ticks)
    return 100.0 * least / (step_ms * 1e-3)

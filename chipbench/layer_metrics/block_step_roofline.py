"""The least time a block decoder's pass could take (the larger of: bytes over the chip's memory bandwidth, operations over its bf16 peak; flops_bytes/<the configuration's family>_block_step.py at the tick records' own n_active, kv_tokens and experts_touched) over the step program's median device time.  Nothing to read on a program whose tick records carry no block_len."""
import importlib
import statistics

import lane_spans
import reduce_helpers as rh

NAME = "block_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None:
        return None
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if "block_len" in t and "experts_touched" in t]
    if step_ms is None or not ticks:
        return None
    cfg, peaks = obs["config"], obs["peaks"]
    step = importlib.import_module(f"flops_bytes.{cfg['family']}_block_step")
    least = statistics.fmean(
        max(step.bytes_needed(cfg, t["n_active"], t["kv_tokens"],
                              t["experts_touched"]) / peaks["hbm_bytes_per_s"],
            step.flops_needed(cfg, t["n_active"], t["kv_tokens"])
            / peaks["bf16_flops_per_s"]) for t in ticks)
    return 100.0 * least / (step_ms * 1e-3)

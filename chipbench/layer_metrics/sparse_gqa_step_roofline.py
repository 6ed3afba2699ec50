"""The least time a decode tick of a model whose grouped-query heads read the keys an indexer selects out of the K/V pools could take (bytes-bound: every weight outside the experts once, the touched experts once, the index keys up to each active slot's position, the selected K and V rows, over the chip's memory bandwidth; the load is the tick records' own n_active, kv_visible, kv_selected and experts_touched) over the step program's median device time.  Nothing to read on a program whose tick records carry no selected_kv_bytes, or under another family's configuration."""
import statistics

import lane_spans
import reduce_helpers as rh
from flops_bytes import keye_vl2_decode_step as decode

NAME = "sparse_gqa_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    if obs["peaks"] is None or "sa_config" not in obs["config"]:
        return None
    step_ms = rh.median_module_ms(obs, "step")
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if "selected_kv_bytes" in t]
    if step_ms is None or not ticks:
        return None
    need = statistics.fmean(
        decode.bytes_needed(obs["config"], t["n_active"], t["kv_visible"],
                            t["kv_selected"], t.get("experts_touched", 0))
        for t in ticks)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (step_ms * 1e-3)

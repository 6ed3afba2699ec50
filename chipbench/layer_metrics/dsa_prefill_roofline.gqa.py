"""The least time the traced window's prefills of a model whose grouped-query heads select what they read could take (compute-bound: the operations needed at the prefill.batch records' true lengths (products, scoring over the causal extent of the rows that choose, attention over the selected keys, experts), each record's by the part of its forward that lies inside the traced window, over the chip's bf16 peak) over the prefill programs' device time in that window.  Nothing to read on a program whose prefill.batch records carry no selected_kv_bytes, or under another family's configuration."""
import lane_spans
import reduce_helpers as rh
from flops_bytes import keye_vl2_prefill as prefill

NAME = "dsa_prefill_roofline.gqa"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"


def read(obs):
    w = obs.get("trace_host_window")
    durs = rh.module_durations(obs, "prefill")
    if obs["peaks"] is None or w is None or not durs \
            or "sa_config" not in obs["config"]:
        return None
    flops = 0.0
    for rec in lane_spans.records(obs, "prefill.batch", from_start=True):
        if "selected_kv_bytes" not in rec or rec["t_ready"] <= rec["t_start"]:
            continue
        inside = min(rec["t_ready"], w[1]) - max(rec["t_start"], w[0])
        if inside > 0:
            flops += prefill.flops_needed(obs["config"], rec["n_tokens"]) \
                * inside / (rec["t_ready"] - rec["t_start"])
    if not flops:
        return None
    return 100.0 * flops / obs["peaks"]["bf16_flops_per_s"] / sum(durs)

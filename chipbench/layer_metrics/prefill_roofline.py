"""The least time the prefills of the traced window could take (compute-bound: required operations over the chip's bf16 peak) over the prefill programs' device time."""
import reduce_helpers as rh
from flops_bytes import llama_prefill as prefill

NAME = "prefill_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ttft_p90_ms"


def read(obs):
    if obs["peaks"] is None:
        return None
    durs = rh.module_durations(obs, "prefill")
    flops = sum(prefill.flops_needed(obs["config"], r["n_prompt"]) for r in obs["requests"]
                if rh.in_traced_window(obs, r["t_first"]))
    if not durs or not flops:
        return None
    return 100.0 * flops / obs["peaks"]["bf16_flops_per_s"] / sum(durs)

"""The Pallas kernel ssm_state_step on chip 0: the least time its calls in the traced window could take (bytes-bound: each call reads and writes the float32 matrices of the active slots of one mixer once, at the window's mean active slots a tick, over the chip's memory bandwidth) over the kernel's device time, found by its name in the trace.  Nothing to read where no operation of that name ran (the step's XLA form, another family's configuration, or a program without the kernel)."""
import re
import statistics

import lane_spans
from flops_bytes import nemotron_h_decode_step as decode

NAME = "ssm_state_step_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "out_tok_per_s"

KERNEL = re.compile(r"^ssm_state_step(\.\d+)?$")


def read(obs):
    if obs["peaks"] is None or obs.get("trace") is None \
            or "mamba_num_heads" not in obs["config"]:
        return None
    chip = obs["trace"]["chips"][0]
    names = [k for k in chip["op_seconds"] if KERNEL.match(k)]
    spent = sum(chip["op_seconds"][k] for k in names)
    calls = sum(chip["op_counts"][k] for k in names)
    active = [t["n_active"] for t in lane_spans.records(obs, "decode.tick")]
    if not spent or not active:
        return None
    least = calls * 2 * statistics.fmean(active) \
        * decode.recurrent_bytes_per_slot(obs["config"]) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / spent

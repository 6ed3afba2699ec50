"""Device time of the prefill programs in the traced window per thousand prompt tokens whose prefill ended in it."""
import reduce_helpers as rh

NAME = "prefill_ms_per_ktok"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "serving device programs"
MOVES = "ttft_p90_ms"


def read(obs):
    durs = rh.module_durations(obs, "prefill")
    toks = sum(r["n_prompt"] for r in obs["requests"] if rh.in_traced_window(obs, r["t_first"]))
    return sum(durs) * 1e3 / (toks / 1000.0) if durs and toks else None

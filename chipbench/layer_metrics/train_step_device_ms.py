"""Device busy time per optimizer step: the busiest chip's busy seconds in the traced window over the optimizer steps that fell in it (the window's own rate)."""
NAME = "train_step_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "trainer"
MOVES = "train_tok_per_s_chip"


def read(obs):
    if obs.get("trace") is None:
        return None
    t = obs["trace"]
    busy = max(t["chips"][c]["busy_s"] for c in sorted(t["chips"])[:obs["chips"]])
    steps = obs["optimizer_steps"] / obs["window_s"] * t["window_s"]
    return busy * 1e3 / steps if steps else None

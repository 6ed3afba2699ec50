"""Of the device's busy time in the traced window of a saturated cell, the share the prefill programs held: how the two lanes divide the chip where long prompts and long answers share it (the prefill programs' device seconds over the union of the device's operation intervals).  Nothing to read without a trace or where no prefill ran in it."""
import reduce_helpers as rh

NAME = "prefill_device_share.sat"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    durs = rh.module_durations(obs, "prefill")
    if not durs:
        return None
    busy = obs["trace"]["chips"][0]["busy_s"]
    return 100.0 * sum(durs) / busy if busy else None

"""One minus the union of the device's operation intervals over the traced window, on the worst chip."""
import reduce_helpers as rh

NAME = "device_idle_share.decode"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "out_tok_per_s"


def read(obs):
    return rh.worst_idle_share(obs)

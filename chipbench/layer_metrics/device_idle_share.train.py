"""One minus the union of the device's operation intervals over the traced window, on the worst chip."""
import reduce_helpers as rh

NAME = "device_idle_share.train"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "train_tok_per_s_chip"


def read(obs):
    return rh.worst_idle_share(obs)

"""Model FLOP/s utilisation: tokens per second per chip of this run times the operations a token needs forward and backward (no recomputation) over the chip's bf16 peak."""
from flops_bytes import bert_train as bert

NAME = "train_mfu"
UNIT = "%"
SOURCE = "host_clock"
LAYER = "device"
MOVES = "train_tok_per_s_chip"


def read(obs):
    if obs["peaks"] is None:
        return None
    rate = obs["tokens"] / obs["window_s"] / obs["chips"]
    return 100.0 * rate * bert.flops_per_token(obs["config"], obs["seq"]) / obs["peaks"]["bf16_flops_per_s"]

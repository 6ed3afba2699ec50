"""Tokens trained per second per chip: every dispatch of the window, from its start to the last loss fetched."""
NAME = "train_tok_per_s_chip"
UNIT = "tokens/s/chip"
SOURCE = "host_clock"


def read(obs):
    return obs["tokens"] / obs["window_s"] / obs["chips"]

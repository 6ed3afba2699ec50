"""Output tokens emitted in the window over the window's length: every token of every request, finished or still decoding when the window closed."""
NAME = "out_tok_per_s"
UNIT = "tokens/s"
SOURCE = "host_clock"


def read(obs):
    return obs["tokens_out"] / obs["window_s"]

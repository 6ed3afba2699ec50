"""90th percentile over requests of the mean gap between a request's output tokens (the program stamps first and last token only)."""
import reduce_helpers as rh

NAME = "tpot_p90_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(obs):
    return rh.percentile([t for t in map(rh.tpot_ms, obs["requests"]) if t is not None], 90)

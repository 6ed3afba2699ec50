"""90th percentile over all requests due in the window of the time from the moment a request was due to its first token."""
import reduce_helpers as rh

NAME = "ttft_p90_ms"
UNIT = "ms"
SOURCE = "host_clock"


def read(obs):
    return rh.percentile([rh.ttft_ms(r, obs) for r in obs["requests"]], 90)

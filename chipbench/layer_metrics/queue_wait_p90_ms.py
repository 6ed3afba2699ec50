"""90th percentile of the wait from due time to the prefill lane taking the request (the program's t_start stamp)."""
import reduce_helpers as rh

NAME = "queue_wait_p90_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "ttft_p90_ms"


def read(obs):
    waits = [(r["t_start"] - r["due"]) * 1e3 for r in obs["requests"]
             if r["due"] is not None and r["t_start"] is not None]
    return rh.percentile(waits, 90)

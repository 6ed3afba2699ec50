"""How late the load generator ran: 90th percentile of actual send minus due time."""
import reduce_helpers as rh

NAME = "gen_late_p90_ms"
UNIT = "ms"
SOURCE = "host_clock"
LAYER = "entry points, load generator"
MOVES = "ttft_p90_ms"


def read(obs):
    late = [(r["sent"] - r["due"]) * 1e3 for r in obs["requests"] if r["due"] is not None]
    return rh.percentile(late, 90)

"""99th percentile over every gap between two consecutive tokens of one request (prefill's first token, then one per decode tick it was active in), all requests pooled: the true inter-token gap, where tpot is a request's mean."""
import lane_spans
import reduce_helpers as rh

NAME = "itl_p99_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "tpot_p90_ms"


def read(obs):
    gaps = lane_spans.token_gaps(obs)
    if not gaps:
        return None
    return rh.percentile([g * 1e3 for per_request in gaps.values()
                          for g in per_request], 99)

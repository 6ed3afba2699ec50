"""Median over finished requests of the mean gap between output tokens; above the knee a tail decides nothing."""
import reduce_helpers as rh

NAME = "tpot_p50_ms.sat"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    return rh.percentile([t for t in map(rh.tpot_ms, obs["requests"]) if t is not None], 50)

"""The host's part of a decode tick (see tick_host_ms) where the prefill lane contends for the device lock and the device."""
import lane_spans

NAME = "tick_host_ms.doc"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "tpot_p90_ms"


def read(obs):
    return lane_spans.tick_host_ms(obs)

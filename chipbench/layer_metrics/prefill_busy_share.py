"""Share of the window in which the prefill lane had a batch in hand (t_start to t_first of the lane log's prefill.batch records, clipped to the window)."""
import lane_spans

NAME = "prefill_busy_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "ttft_p90_ms"


def read(obs):
    return lane_spans.clipped_share(obs, "prefill.batch", "t_start", "t_first")

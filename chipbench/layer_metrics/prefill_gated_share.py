"""Share of the window in which requests were queued but the prefill lane could admit none: no free slot, block or token budget (the lane log's prefill.gated records, clipped to the window)."""
import lane_spans

NAME = "prefill_gated_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "serving host"
MOVES = "ttft_p90_ms"


def read(obs):
    if not lane_spans.records(obs, "prefill.batch"):
        return None     # no lane log, or no prefill at all: nothing to read
    return lane_spans.clipped_share(obs, "prefill.gated", "t0", "t1") or 0.0

"""Mean over the window's prefill batches of the slots that stood free when the lane took the batch (free_slots in the prefill.batch record): above 1 at a prefill batch of 1, an admission that a wider batch would have shared."""
import turn_spans

NAME = "free_slots_at_admit"
UNIT = "slots"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    batches = turn_spans.ending_in_window(obs, "prefill.batch", "t_first", field="free_slots")
    if not batches:
        return None
    return sum(b["free_slots"] for b in batches) / len(batches)

"""Slots held and left out of a decode step for want of a block (the tick records' n_parked: a slot whose grant the manager's safe-state rule refused, parked for that step and asked about again at the next), summed over the window's ticks, over ticks x num_slots: what growth on demand leaves standing that an eviction would set stepping.  Nothing to read on a program whose tick records carry no n_parked."""
import lane_spans

NAME = "parked_slot_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving host"
MOVES = "out_tok_per_s"


def read(obs):
    parked = [t["n_parked"] for t in lane_spans.records(obs, "decode.tick")
              if "n_parked" in t]
    if not parked:
        return None
    return 100.0 * sum(parked) / (len(parked) * obs["num_slots"])

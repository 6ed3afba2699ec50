"""Of the positions the window's decode ticks could see, the share they read: the tick records' kv_selected over kv_visible, summed over the ticks (a layer; every layer selects alike).  100 would mean nothing is selected.  Nothing to read on a program whose tick records carry no kv_selected."""
import lane_spans

NAME = "dsa_selected_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "tpot_p90_ms"


def read(obs):
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if t.get("kv_visible")]
    if not ticks:
        return None
    return 100.0 * sum(t["kv_selected"] for t in ticks) \
        / sum(t["kv_visible"] for t in ticks)

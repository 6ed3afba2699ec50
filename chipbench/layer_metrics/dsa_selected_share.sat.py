"""Of the positions the window's decode ticks of a saturated cell could see, the share they read: the tick records' kv_selected over kv_visible, summed over the ticks (a layer; every layer selects alike).  100 would mean nothing is selected.  Nothing to read on a program whose tick records carry no kv_selected."""
import lane_spans

NAME = "dsa_selected_share.sat"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    ticks = [t for t in lane_spans.records(obs, "decode.tick")
             if t.get("kv_visible")]
    if not ticks:
        return None
    return 100.0 * sum(t["kv_selected"] for t in ticks) \
        / sum(t["kv_visible"] for t in ticks)

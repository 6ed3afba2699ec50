"""Over the window's decode ticks of a model whose layers select what they read out of the K/V pools, the bytes the scoring and the selected attention had to read (the tick records' index_key_bytes + selected_kv_bytes: an index key a visible position, K and V rows a selected position, over the layers) over all the bytes the ticks needed (flops_bytes/keye_vl2_decode_step.py: the weights outside the experts, the touched experts, those rows): how much of a tick's traffic is the selection's, whatever the contexts' lengths.  Nothing to read on a program whose tick records carry no selected_kv_bytes, or under another family's configuration."""
import lane_spans
from flops_bytes import keye_vl2_decode_step as decode

NAME = "selection_bytes_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "serving device programs"
MOVES = "out_tok_per_s"


def read(obs):
    if "sa_config" not in obs["config"]:
        return None
    read_ = needed = 0
    for t in lane_spans.records(obs, "decode.tick"):
        if "selected_kv_bytes" not in t:
            continue
        read_ += t["index_key_bytes"] + t["selected_kv_bytes"]
        needed += decode.bytes_needed(obs["config"], t["n_active"],
                                      t["kv_visible"], t["kv_selected"],
                                      t.get("experts_touched", 0))
    return 100.0 * read_ / needed if needed else None

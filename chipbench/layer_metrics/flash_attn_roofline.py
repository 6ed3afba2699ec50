"""The three Mosaic flash-attention kernels (forward, dq, dkv) on chip 0: for each call the larger of its operation time and its byte time at the chip's peaks, summed, over the kernels' device time."""
from flops_bytes import flash_attention as flash

NAME = "flash_attn_roofline"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tok_per_s_chip"


def read(obs):
    if obs["peaks"] is None:
        return None
    if obs.get("trace") is None:
        return None
    cfg, pk = obs["config"], obs["peaks"]
    heads = cfg["num_attention_heads"]
    shape = (obs["rows"] // obs["chips"], heads, obs["seq"], cfg["hidden_size"] // heads)
    least = spent = 0.0
    for kind, durs in obs["trace"]["chips"][0]["mosaic"].items():
        try:
            flops, nbytes = flash.needs(kind, *shape)
        except KeyError:
            continue
        least += len(durs) * max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
        spent += sum(durs)
    return 100.0 * least / spent if spent else None

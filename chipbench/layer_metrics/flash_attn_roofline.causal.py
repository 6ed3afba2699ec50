"""The three Mosaic flash-attention kernels (forward, dq, dkv) of a causal trainer at latent attention's expanded heads on chip 0: every Mosaic call that is not a grouped expert kernel (those carry their name), each owing the larger of its operation time (the causal half) and its byte time at the chip's peaks (flops_bytes/flash_attention_mla.py), over their device time."""
from flops_bytes import flash_attention_mla as flash

NAME = "flash_attn_roofline.causal"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "train_tok_per_s_chip"

GROUPED = "grouped_expert_ffn"


def flash_calls(obs):
    """(calls, seconds) of the Mosaic kernels on chip 0 that are flash
    kernels: all of them less the grouped ones, which are found by name."""
    chip = obs["trace"]["chips"][0]
    calls = sum(len(d) for d in chip["mosaic"].values())
    spent = sum(sum(d) for d in chip["mosaic"].values())
    for name, n in chip["op_counts"].items():
        if GROUPED in name:
            calls -= n
            spent -= chip["op_seconds"][name]
    return calls, spent


def read(obs):
    if obs["peaks"] is None or obs.get("trace") is None:
        return None
    cfg, pk = obs["config"], obs["peaks"]
    if "qk_nope_head_dim" not in cfg:
        return None
    calls, spent = flash_calls(obs)
    if calls <= 0 or spent <= 0:
        return None
    flops, nbytes = flash.needs(
        obs["rows"] // obs["chips"], cfg["num_attention_heads"], obs["seq"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    least = calls * max(flops / pk["bf16_flops_per_s"],
                        nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent

"""Parameters held and floating-point operations a token needs in one training
step of the JoyAI-LLM-Flash cut, from the configuration file alone.

``flops_per_token``: forward and backward (three times the forward's matrix
products), no recomputation counted: the latent projections, causal
attention's two products over the mean of ``seq / 2`` keys at heads of (nope +
rope) and v, the dense layer's SwiGLU, an expert layer's router, shared expert
and the (row, expert) pairs that fall on the HELD experts (``pairs_per_token``,
a layer: ``num_experts_per_tok x held / router_experts`` unless the run's own
count is given), the prediction module (``W_eh`` and one expert layer) and the
two heads over the held vocabulary.
"""


def _sizes(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    attn = (h * ql + ql * nh * (dn + dr) + h * (kl + dr)
            + kl * nh * (dn + dv) + nh * dv * h)
    expert = 3 * h * cfg["moe_intermediate_size"]
    return {"attn": attn, "expert": expert,
            "shared": cfg["n_shared_experts"] * expert,
            "router": cfg["router_experts"] * h,
            "dense_ffn": 3 * h * cfg["intermediate_size"],
            "vocab": cfg["vocab_size"] * h, "eh": 2 * h * h,
            "heads": nh * (dn + dr + dv)}


def param_table(cfg):
    """Matrix parameters held on this chip, by part (norm weights, a few
    thousand a layer, and the choice bias are left out)."""
    s = _sizes(cfg)
    held = cfg["experts_held"][1]
    expert_layer = s["attn"] + s["shared"] + s["router"] + held * s["expert"]
    n_expert = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    out = {"embedding_and_head": 2 * s["vocab"],
           "dense_layers": cfg["first_k_dense_replace"]
           * (s["attn"] + s["dense_ffn"]),
           "expert_layers": n_expert * expert_layer,
           "prediction_module": cfg["num_nextn_predict_layers"]
           * (s["eh"] + expert_layer)}
    out["total"] = sum(out.values())
    return out


def forward_flops_per_token(cfg, seq, pairs_per_token=None):
    """By part, one token's forward products."""
    s = _sizes(cfg)
    if pairs_per_token is None:
        pairs_per_token = (cfg["num_experts_per_tok"] * cfg["experts_held"][1]
                           / cfg["router_experts"])
    attention = 2 * (seq / 2.0) * s["heads"]
    expert_layer = (2 * (s["attn"] + s["shared"] + s["router"]) + attention
                    + 2 * pairs_per_token * s["expert"])
    n_expert = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return {"dense_layers": cfg["first_k_dense_replace"]
            * (2 * (s["attn"] + s["dense_ffn"]) + attention),
            "expert_layers": n_expert * expert_layer,
            "prediction_module": cfg["num_nextn_predict_layers"]
            * (2 * s["eh"] + expert_layer),
            "heads": (1 + cfg["num_nextn_predict_layers"]) * 2 * s["vocab"]}


def flops_per_token(cfg, seq, pairs_per_token=None):
    return 3 * sum(forward_flops_per_token(cfg, seq, pairs_per_token).values())

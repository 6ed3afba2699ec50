"""Floating-point operations one prompt of ``n`` tokens needs in the prefill of a
GLM-MoE-DSA model, from its shapes: every matrix product over n rows (latent
attention's five, the indexer's three, the dense or the shared expert, the
router, and of the routed experts the share held here: ``num_experts_per_tok x
held / router_experts`` experts a token, 0.5 in the cell), the indexer's scores
over the causal half (``2 x index_n_heads x index_head_dim`` a pair), attention
over the ``min(t, index_topk)`` selected keys of row ``t``, and the head on the
last row only.  Where the absorbed and the expanded form of latent attention
differ the cheaper is counted: a pair costs ``2 x heads x (nope + rope + v)`` in
the expanded form (``2 x heads x (2 x rank + rope)`` in the absorbed one), and
``W_kvb`` is applied once a token either way.  Padding to a bucket, experts not
chosen and keys not selected are not needed by the algorithm and not counted."""


def linear_params(cfg):
    """-> (latent attention, indexer, one expert, router, dense feed-forward):
    parameters of the matrices a token passes, norms left out."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mla = h * ql + ql * nh * (dn + dr) + h * (kl + dr) + kl * nh * (dn + dv) \
        + nh * dv * h
    idx = ql * cfg["index_n_heads"] * cfg["index_head_dim"] \
        + h * cfg["index_head_dim"] + h * cfg["index_n_heads"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    return mla, idx, expert, h * cfg["router_experts"], 3 * h * cfg["intermediate_size"]


def held_experts_per_token(cfg):
    return cfg["num_experts_per_tok"] * cfg["experts_held"][1] / cfg["router_experts"]


def params_per_token(cfg):
    """Parameters one token's products read, over the layers (head left out)."""
    mla, idx, expert, router, dense = linear_params(cfg)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    moe = cfg["n_shared_experts"] * expert + router \
        + held_experts_per_token(cfg) * expert
    return cfg["num_hidden_layers"] * (mla + idx) + n_dense * dense + n_moe * moe


def selected_pairs(n, topk):
    """Sum over rows t = 1..n of min(t, topk): the (query, key) pairs read."""
    m = min(n, topk)
    return m * (m + 1) // 2 + (n - m) * topk


def flops_needed(cfg, n):
    nh = cfg["num_attention_heads"]
    per_pair = 2 * nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                         + cfg["v_head_dim"])
    scores = 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * (n * (n + 1) // 2)
    attn = per_pair * selected_pairs(n, cfg["index_topk"])
    return 2 * params_per_token(cfg) * n \
        + cfg["num_hidden_layers"] * (scores + attn) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]

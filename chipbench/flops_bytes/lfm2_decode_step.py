"""What one decode tick of an LFM2-MoE model needs, from its shapes: every
non-expert weight once, the TOUCHED experts' weights once (an expert no row
was routed to need not be read), the embedding rows of the active slots, the
attention layers' keys and values at the active slots' true lengths (read)
plus one new token's (written), and the conv layers' states of the active
slots, read and written.  A program that streams every expert whatever the
routing, or gathers a whole view of the cache, reads low."""


def _kinds(cfg):
    """(conv layers, attention layers, dense layers, expert layers)."""
    conv = cfg["layer_types"].count("conv")
    dense = cfg["num_dense_layers"]
    return (conv, cfg["num_hidden_layers"] - conv, dense,
            cfg["num_hidden_layers"] - dense)


def expert_bytes(cfg, itemsize=2):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def fixed_weight_bytes(cfg, itemsize=2):
    """Every weight a tick reads whatever the routing: the (tied) embedding as
    the head, the final norm, each layer's norms and operator, the dense
    feed-forwards, the routers and their biases."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    conv, attn, dense, moe = _kinds(cfg)
    conv_op = 3 * h * h + h * h + cfg["conv_L_cache"] * h
    attn_op = h * hd * (2 * nq + 2 * nkv) + 2 * hd
    n = cfg["vocab_size"] * h + h + cfg["num_hidden_layers"] * 2 * h \
        + conv * conv_op + attn * attn_op \
        + dense * 3 * h * cfg["intermediate_size"] \
        + moe * (cfg["num_experts"] * h + cfg["num_experts"])
    return n * itemsize


def weight_bytes(cfg, itemsize=2):
    """All of the model as served (PERF.md's byte table)."""
    moe = _kinds(cfg)[3]
    return fixed_weight_bytes(cfg, itemsize) \
        + moe * cfg["num_experts"] * expert_bytes(cfg, itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * _kinds(cfg)[1] * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * itemsize


def state_bytes_per_slot(cfg, itemsize=2):
    return _kinds(cfg)[0] * cfg["conv_L_cache"] * cfg["hidden_size"] * itemsize


def bytes_needed(cfg, active_slots, kv_tokens, experts_touched, itemsize=2):
    """``experts_touched``: over the expert layers, the sum of experts that
    received a row (the tick records' field of that name)."""
    return fixed_weight_bytes(cfg, itemsize) \
        + experts_touched * expert_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + (kv_tokens + active_slots) * kv_bytes_per_token(cfg, itemsize) \
        + 2 * active_slots * state_bytes_per_slot(cfg, itemsize)

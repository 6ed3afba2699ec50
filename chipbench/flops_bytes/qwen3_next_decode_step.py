"""What one decode tick of a Qwen3-Next model needs, from its shapes.  Bytes:
every weight outside the routed experts once (the head whole, the embedding
rows of the active slots), the TOUCHED held experts' weights once (an expert no
row was routed to need not be read), each active slot's per-slot states (a
delta-rule layer's float32 matrices and its convolution ring) read and written
once, the attention layers' keys and values at the active slots' true lengths
(read) plus one new token's (written).  Operations: a row's products, with the
``num_experts_per_tok * held / router_experts`` routed experts that lie here.
A program that streams every held expert whatever the routing, reads a state
twice, or computes every expert on every row reads low."""


def layer_counts(cfg):
    """(delta-rule layers, attention layers)."""
    attn = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - attn, attn


def _dims(cfg):
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return kd, vd, 2 * kd + vd


def operator_params(cfg):
    """-> (a delta-rule operator's, an attention operator's) parameters: the
    projections, the convolution, the gates' vectors and the head norms."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nv = cfg["linear_num_value_heads"]
    _kd, vd, conv = _dims(cfg)
    delta = h * (conv + vd) + h * 2 * nv + cfg["linear_conv_kernel_dim"] * conv \
        + vd * h + 2 * nv + cfg["linear_value_head_dim"]
    attn = h * nq * 2 * hd + 2 * h * nkv * hd + nq * hd * h + 2 * hd
    return delta, attn


def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_fixed_params(cfg):
    """What every layer holds beside its operator and its routed experts: two
    norms, the router over ALL experts, the shared expert and its gate."""
    h = cfg["hidden_size"]
    return 2 * h + cfg["router_experts"] * h \
        + 3 * h * cfg["shared_expert_intermediate_size"] + h


def layer_params(cfg):
    """-> (a delta-rule layer's, an attention layer's) parameters HELD here:
    PERF.md's parameter table."""
    delta, attn = operator_params(cfg)
    rest = layer_fixed_params(cfg) + cfg["experts_held"][1] * expert_params(cfg)
    return delta + rest, attn + rest


def expert_bytes(cfg, itemsize=2):
    return expert_params(cfg) * itemsize


def fixed_weight_bytes(cfg, itemsize=2):
    """Every weight a tick reads whatever the routing and the slots: all but
    the routed experts' and the embedding table."""
    n_delta, n_attn = layer_counts(cfg)
    delta, attn = operator_params(cfg)
    n = cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"] \
        + n_delta * delta + n_attn * attn \
        + cfg["num_hidden_layers"] * layer_fixed_params(cfg)
    return n * itemsize


def weight_bytes(cfg, itemsize=2):
    """All of the model as served here."""
    return fixed_weight_bytes(cfg, itemsize) \
        + cfg["vocab_size"] * cfg["hidden_size"] * itemsize \
        + cfg["num_hidden_layers"] * cfg["experts_held"][1] \
        * expert_bytes(cfg, itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * layer_counts(cfg)[1] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def recurrent_bytes_per_slot(cfg):
    """One delta-rule layer's float32 matrices of one slot."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * 4


def state_bytes_per_slot(cfg, itemsize=2):
    """Over the delta-rule layers: the float32 matrices and the ring of the
    convolution's last ``taps - 1`` inputs in the weights' dtype."""
    ring = (cfg["linear_conv_kernel_dim"] - 1) * _dims(cfg)[2] * itemsize
    return layer_counts(cfg)[0] * (recurrent_bytes_per_slot(cfg) + ring)


def bytes_needed(cfg, active_slots, kv_tokens, experts_touched, state_bytes=None,
                 itemsize=2):
    """``experts_touched``: over the layers, the sum of HELD experts that
    received a row (a count over the router's width is cut to what the bank
    holds); ``state_bytes``: the tick record's field of that name (per-slot
    state the active slots' layers read and wrote), by default twice the
    active slots' states."""
    if state_bytes is None:
        state_bytes = 2 * active_slots * state_bytes_per_slot(cfg, itemsize)
    touched = min(experts_touched,
                  cfg["num_hidden_layers"] * cfg["experts_held"][1])
    return fixed_weight_bytes(cfg, itemsize) \
        + touched * expert_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + (kv_tokens + active_slots) * kv_bytes_per_token(cfg, itemsize) \
        + state_bytes


def flops_needed(cfg, active_slots, kv_tokens):
    """Multiply-adds counted twice.  A row: every layer's operator matrices,
    router, shared expert and the routed experts that lie here (``k * held /
    router_experts`` of them), the head; a delta-rule layer's three products
    over a head's matrix (the read at k, the write, the read at q); an
    attention layer's scores and context over the slot's ``kv_tokens`` share."""
    h = cfg["hidden_size"]
    n_delta, n_attn = layer_counts(cfg)
    delta, attn = operator_params(cfg)
    here = cfg["num_experts_per_tok"] * cfg["experts_held"][1] \
        / cfg["router_experts"]
    per_row = n_delta * delta + n_attn * attn \
        + cfg["num_hidden_layers"] * (layer_fixed_params(cfg) - 2 * h
                                      + here * expert_params(cfg)) \
        + cfg["vocab_size"] * h \
        + n_delta * 3 * recurrent_bytes_per_slot(cfg) // 4
    attend = n_attn * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * kv_tokens
    return 2 * (active_slots * per_row + attend)

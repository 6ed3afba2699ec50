"""What one decode tick of a Nemotron-H model needs, from its shapes and the
tick's own counts: the same work whatever implements it.  Bytes: every weight
outside the routed experts once (the head whole, the embedding rows of the
active slots), the TOUCHED held experts' two matrices once (an expert no row was
routed to need not be read), each active slot's per-slot states (a mixer's
float32 matrices and its convolution ring) read and written once, the attention
layers' keys and values at the active slots' true lengths (read) plus one new
token's (written).  Operations: a row's products, with the ``num_experts_per_tok
* held / router_experts`` routed experts that lie here, in the latent width.  A
program that streams every held expert whatever the routing, reads a state
twice, or computes every expert on every row reads low."""


def layer_counts(cfg):
    """(mixers, attention layers, expert layers) of the pattern."""
    p = cfg["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def _dims(cfg):
    """(d_inner, channels through the convolution)."""
    di = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return di, di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mixer_params(cfg):
    """A Mamba-2 layer: its norm, the in projection, the convolution and its
    bias, A_log, dt_bias and D, the gated norm, the out projection."""
    h, nh = cfg["hidden_size"], cfg["mamba_num_heads"]
    di, conv = _dims(cfg)
    return h + h * (di + conv + nh) + (cfg["conv_kernel"] + 1) * conv + 3 * nh \
        + di + di * h


def attention_params(cfg):
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h + 2 * h * nq * hd + 2 * h * nkv * hd


def expert_params(cfg):
    """One routed expert's two matrices, in the latent width."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_fixed_params(cfg):
    """An expert layer outside its routed experts: its norm, the router over
    ALL experts and the choice bias, the two latent projections, the shared
    expert."""
    h = cfg["hidden_size"]
    return h + cfg["router_experts"] * (h + 1) \
        + 2 * cfg["moe_latent_size"] * h \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]


def expert_layer_params(cfg):
    """An expert layer as HELD here: PERF.md's parameter table."""
    return expert_layer_fixed_params(cfg) \
        + cfg["experts_held"][1] * expert_params(cfg)


def expert_bytes(cfg, itemsize=2):
    return expert_params(cfg) * itemsize


def fixed_weight_bytes(cfg, itemsize=2):
    """Every weight a tick reads whatever the routing and the slots: all but
    the routed experts' and the embedding table."""
    m, a, e = layer_counts(cfg)
    n = cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"] \
        + m * mixer_params(cfg) + a * attention_params(cfg) \
        + e * expert_layer_fixed_params(cfg)
    return n * itemsize


def weight_bytes(cfg, itemsize=2):
    """All of the model as served here."""
    return fixed_weight_bytes(cfg, itemsize) \
        + cfg["vocab_size"] * cfg["hidden_size"] * itemsize \
        + layer_counts(cfg)[2] * cfg["experts_held"][1] \
        * expert_bytes(cfg, itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * layer_counts(cfg)[1] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def recurrent_bytes_per_slot(cfg):
    """One mixer's float32 matrices of one slot."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"] * 4


def state_bytes_per_slot(cfg, itemsize=2):
    """Over the mixers: the float32 matrices and the ring of the
    convolution's last ``taps - 1`` inputs in the weights' dtype."""
    ring = (cfg["conv_kernel"] - 1) * _dims(cfg)[1] * itemsize
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
                  layer_counts(cfg)[2] * cfg["experts_held"][1])
    return fixed_weight_bytes(cfg, itemsize) \
        + touched * expert_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + (kv_tokens + active_slots) * kv_bytes_per_token(cfg, itemsize) \
        + state_bytes


def flops_needed(cfg, active_slots, kv_tokens):
    """Multiply-adds counted twice.  A row: every layer's matrices, the router,
    the latent projections, the shared expert and the routed experts that lie
    here (``k * held / router_experts`` of them), the head; a mixer's two
    products over a head's matrix (the write, the read at C); an attention
    layer's scores and context over the slot's ``kv_tokens`` share."""
    h = cfg["hidden_size"]
    m, a, e = layer_counts(cfg)
    here = cfg["num_experts_per_tok"] * cfg["experts_held"][1] \
        / cfg["router_experts"]
    per_row = m * mixer_params(cfg) + a * attention_params(cfg) \
        + e * (expert_layer_fixed_params(cfg) + here * expert_params(cfg)) \
        + cfg["vocab_size"] * h \
        + m * 2 * recurrent_bytes_per_slot(cfg) // 4
    attend = a * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * kv_tokens
    return 2 * (active_slots * per_row + attend)

"""What one pass of an SDAR-MoE model over its slots' blocks needs, from its
shapes.  Bytes: every non-expert weight once (the embedding rows of the pass's
tokens, not the table; the head whole), the TOUCHED experts' weights once (an
expert no row was routed to need not be read), the keys and values the pass
attends, at the slots' true lengths, read once a slot, and the block's own
written.  Operations: a row's projections, its attention over the rows it
sees, its router scores, its ``num_experts_per_tok`` experts and the head.
What the algorithm needs, not what the program does: a program that computes
every expert on every row, or streams every expert whatever the routing, reads
low."""


def expert_bytes(cfg, itemsize=2):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def _attn_params(cfg):
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    return h * hd * 2 * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"]) + 2 * hd


def fixed_weight_bytes(cfg, itemsize=2, embedding=True):
    """Every weight outside the experts: the head, the final norm, each layer's
    two norms, attention (q, k, v, o and the two head norms) and router; with
    ``embedding`` the embedding table too (a pass reads only its tokens'
    rows)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    n = cfg["vocab_size"] * h * (2 if embedding else 1) + h \
        + layers * (2 * h + _attn_params(cfg) + cfg["num_experts"] * h)
    return n * itemsize


def weight_bytes(cfg, itemsize=2):
    """All of the model as served (PERF.md's byte table)."""
    return fixed_weight_bytes(cfg, itemsize) \
        + cfg["num_hidden_layers"] * cfg["num_experts"] \
        * expert_bytes(cfg, itemsize)


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def bytes_needed(cfg, active_slots, kv_tokens, experts_touched, itemsize=2):
    """``kv_tokens``: K/V rows attended, each slot's block end summed over the
    active slots; ``experts_touched``: over the layers, the sum of experts that
    received a row (the tick records' fields of those names)."""
    rows = active_slots * cfg["block_length"]
    return fixed_weight_bytes(cfg, itemsize, embedding=False) \
        + experts_touched * expert_bytes(cfg, itemsize) \
        + rows * cfg["hidden_size"] * itemsize \
        + (kv_tokens + rows) * kv_bytes_per_token(cfg, itemsize)


def flops_needed(cfg, active_slots, kv_tokens):
    """Multiply-adds counted twice.  A row attends its slot's ``kv_tokens``
    share: scores and context over ``num_attention_heads`` heads."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    rows = active_slots * cfg["block_length"]
    per_row = cfg["num_hidden_layers"] * (
        _attn_params(cfg) - 2 * hd + cfg["num_experts"] * h
        + cfg["num_experts_per_tok"] * 3 * h * cfg["moe_intermediate_size"]) \
        + cfg["vocab_size"] * h
    attend = cfg["num_hidden_layers"] * 2 * cfg["num_attention_heads"] * hd \
        * kv_tokens * cfg["block_length"]
    return 2 * (rows * per_row + attend)

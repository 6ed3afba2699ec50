"""What one decode tick of the Ouro looped decoder needs, from its shapes: the
stack's weights once A PASS (they do not fit on the chip's fast memory, so a
pass reads them again), the head and the final norm's weight once, the
embedding rows of the active slots, and the keys and values of the active slots
at their true lengths, a set a pass (read) plus one new token's (written).  A
program that gathers or copies more than this reads low."""


def layer_params(cfg):
    """q, k, v, o; the SwiGLU's three; the four norms."""
    h, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * hd * (2 * nq + 2 * nkv) + 3 * h * f + 4 * h


def stack_params(cfg):
    return cfg["num_hidden_layers"] * layer_params(cfg)


def table_params(cfg):
    """The stack, the embedding and the untied head: the configuration's
    parameter table.  The final norm (hidden) and the exit gate (hidden + 1),
    which the served forward never reads, are ``other_params``."""
    return stack_params(cfg) + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def other_params(cfg):
    return 2 * cfg["hidden_size"] + 1


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg["total_ut_steps"] * cfg["num_hidden_layers"] \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def weight_bytes(cfg, itemsize=2):
    """Read a tick: the stack a pass, the head and the final norm once."""
    h = cfg["hidden_size"]
    return (cfg["total_ut_steps"] * stack_params(cfg)
            + cfg["vocab_size"] * h + h) * itemsize


def bytes_needed(cfg, active_slots, kv_tokens, itemsize=2):
    return weight_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + (kv_tokens + active_slots) * kv_bytes_per_token(cfg, itemsize)


def flops_needed(cfg, active_slots, kv_tokens):
    """Multiply-adds counted twice: a row's products with every matrix of the
    stack a pass and with the head once, and a pass's scores and context over
    the slot's share of ``kv_tokens``."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    matrices = layer_params(cfg) - 4 * h
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    rows = 2 * active_slots * (passes * layers * matrices
                               + cfg["vocab_size"] * h)
    attend = passes * layers * 2 * 2 * cfg["num_attention_heads"] * hd \
        * kv_tokens
    return rows + attend

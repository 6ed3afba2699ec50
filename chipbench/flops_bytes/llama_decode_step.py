"""What one decode tick of a Llama-family model needs, from its shapes: every
weight matrix once, the embedding rows of the active slots, and the keys and
values of the active slots at their true lengths (read) plus one new token's
(written).  A program that gathers or copies more than this reads low."""


def weight_bytes(cfg, itemsize=2):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd, nq, nkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = h * hd * (2 * nq + 2 * nkv) + 3 * h * f + 2 * h
    return (cfg["num_hidden_layers"] * layer + v * h + h) * itemsize


def kv_bytes_per_token(cfg, itemsize=2):
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def bytes_needed(cfg, active_slots, kv_tokens, itemsize=2):
    return weight_bytes(cfg, itemsize) \
        + active_slots * cfg["hidden_size"] * itemsize \
        + (kv_tokens + active_slots) * kv_bytes_per_token(cfg, itemsize)


"""Floating-point operations one prompt of ``n`` tokens needs in prefill: every
matrix multiplication over n rows, causal attention over n(n+1)/2 pairs, and
the output head on the last row only.  Padding to a bucket is not needed by the
algorithm and is not counted."""


def flops_needed(cfg, n):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd, nq, nkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer_mat = h * hd * (2 * nq + 2 * nkv) + 3 * h * f
    attn = 4 * nq * hd * (n * (n + 1) // 2)         # QK^T and PV, causal half
    return cfg["num_hidden_layers"] * (2 * layer_mat * n + attn) + 2 * v * h

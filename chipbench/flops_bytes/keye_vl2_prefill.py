"""Floating-point operations one prompt of ``n`` tokens needs in the prefill of
Keye-VL-2.0's language model, from its shapes: every matrix product over n rows
(attention's four, the indexer's three, the router, ``num_experts_per_tok``
experts a token), the indexer's scores over the causal extent of the rows that
see more than ``topk`` positions (``2 x indexer_num_heads x indexer_head_dim`` a
pair: a row that sees no more than ``topk`` reads all of them and needs no
score), grouped-query attention over the ``min(t, topk)`` selected keys of row
``t`` (``4 x heads x head_dim`` a pair: the scores and the weighted values), and
the head on the last row only.  Padding to a bucket, experts not chosen and keys
not selected are not needed by the algorithm and not counted."""


def linear_params(cfg):
    """-> (attention, indexer, one expert, router): parameters of the matrices
    a token passes, norms left out."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    attn = 2 * h * nq * hd + 2 * h * nkv * hd
    idx = h * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + h * sa["indexer_head_dim"] + h * sa["indexer_num_heads"]
    return attn, idx, 3 * h * cfg["moe_intermediate_size"], h * cfg["num_experts"]


def params_per_token(cfg):
    """Parameters one token's products read, over the layers (head left out)."""
    attn, idx, expert, router = linear_params(cfg)
    return cfg["num_hidden_layers"] * (
        attn + idx + router + cfg["num_experts_per_tok"] * expert)


def selected_pairs(n, topk):
    """Sum over rows t = 1..n of min(t, topk): the (query, key) pairs read."""
    m = min(n, topk)
    return m * (m + 1) // 2 + (n - m) * topk


def scored_pairs(n, topk):
    """Sum of t over the rows t = topk + 1..n: the (query, index key) pairs of
    the rows that have to choose."""
    m = min(n, topk)
    return n * (n + 1) // 2 - m * (m + 1) // 2


def flops_needed(cfg, n):
    sa = cfg["sa_config"]
    scores = 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * scored_pairs(n, sa["topk"])
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * selected_pairs(n, sa["topk"])
    return 2 * params_per_token(cfg) * n \
        + cfg["num_hidden_layers"] * (scores + attn) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]

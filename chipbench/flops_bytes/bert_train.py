"""Floating-point operations one token of BERT pretraining needs, forward and
backward (three times the forward's matrix products), no recomputation: the
encoder's and the masked-LM head's matrix multiplications and attention's two
products over the sequence.  The pooler and the next-sentence head see one
token in a sequence and are left out."""


def flops_per_token(cfg, seq):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    mat = cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f) + h * h + v * h
    attn = cfg["num_hidden_layers"] * 4 * seq * h     # QK^T and PV, per token
    return 3 * (2 * mat + attn)

"""Operations and bytes of one call of a training flash-attention kernel over
latent attention's expanded heads, (B, H, T, D) for q and k and (B, H, T, Dv)
for v, causal, as the algorithm needs them.  Each of the three kernels owes two
products over the causal half of the (T, T) scores, one D wide and one Dv wide:
the forward QK^T and PV; ``dq`` dP and dQ; ``dkv`` dV and dK (recomputing the
scores is the kernels' choice and is not counted).  Bytes are each operand and
result once; the forward's, the fewest of the three, stand for all (at these
lengths every one of them is bound by its operations)."""


def needs(b, h, t, d, dv, itemsize=2):
    flops = 2 * b * h * (t * (t + 1) // 2) * (d + dv)
    nbytes = b * h * t * (2 * d + 2 * dv) * itemsize + b * h * t * 4
    return flops, nbytes

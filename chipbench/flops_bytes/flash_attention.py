"""Operations and bytes of the three flash-attention kernels for one call over
(B, H, T, D), non-causal, as the algorithm needs them: the forward computes
QK^T and PV; the backward needs dV, dP, dQ and dK (recomputing the scores is
the kernels' choice and is not counted): dP and dQ go to ``dq``, dV and dK to
``dkv``.  Bytes are each operand and result once."""


def needs(kind, b, h, t, d, itemsize=2):
    mm = 2 * b * h * t * t * d                 # one (T,T,D) matrix product
    tensor = b * h * t * d * itemsize
    lse = b * h * t * 4
    if kind == "flash_fwd":
        return 2 * mm, 4 * tensor + lse                  # q k v -> o, lse
    if kind == "flash_dq":
        return 2 * mm, 6 * tensor + 2 * lse              # q k v o do -> dq
    if kind == "flash_dkv":
        return 2 * mm, 7 * tensor + 2 * lse              # q k v o do -> dk dv
    raise KeyError(kind)

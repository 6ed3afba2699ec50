"""Operations and bytes of the trained grouped expert feed-forward, a step, as
the algorithm needs them.  A (row, expert) pair that falls on a held expert owes
its three products forward (``2 x 3 x H x I``) and six backward (the input's
gradient through three, the banks' gradients through three): forward, the
forward again where the layer is recomputed, and backward.  Bytes: the banks of
the touched experts once a kernel (one forward kernel, one backward kernel
reading three banks, one writing three float32 gradients) and the pairs' rows in
and out."""


def needs(cfg, pairs, layers, recompute=True, itemsize=2):
    """``pairs``: held (row, expert) pairs a step, over the ``layers`` expert
    layers -> (operations, bytes) of a step's grouped kernels."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    forwards = 2 if recompute else 1
    flops = pairs * 2 * 3 * h * i * (forwards + 2)
    bank = 3 * held * h * i
    nbytes = layers * bank * (itemsize * (forwards + 1) + 4) \
        + pairs * h * (itemsize + 4) * (forwards + 2)
    return flops, nbytes

#!/usr/bin/env python
"""Pallas fused 1x1-conv+BN+ReLU probe — the PERF_NOTES ceiling
question (VERDICT r3 item 9): ResNet-50's non-conv time is
bandwidth-bound elementwise/norm traffic between convs; can a
hand-fused Pallas kernel beat XLA's conv+BN+ReLU fusion?

The probe fuses the bottleneck block's 1x1 conv (half its FLOPs; as a
matmul it is exactly MXU-shaped) with the folded BN affine and the ReLU
in ONE Pallas kernel: out = relu(scale_n * (x @ w) + bias_n), written
bf16, scores tiled in VMEM.  The XLA baseline is the framework's own
Convolution+BatchNorm(inference)+relu chain — what bench.py's ResNet
actually runs per block.

Both paths are timed from the SAME NCHW logical input with the
scan-slope harness (benchmark/opperf.py — dispatch-return-proof), so
the Pallas path pays its NCHW<->NHWC transposes honestly.

Run on chip:  python tools/pallas_conv_probe.py          (prints JSON)
Numerics only: ... --check  (small shape, pallas INTERPRET mode — the
caller asks for it; without --check the kernel is compiled for the TPU
and a process without one fails)
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def fused_matmul_affine_relu(x, w, scale, bias, block_m=512,
                             block_n=256, block_k=256, interpret=False):
    """relu(scale * (x @ w) + bias) as one Pallas kernel.

    x (M, K) bf16, w (K, N) bf16, scale/bias (N,) f32 -> (M, N) bf16.
    f32 accumulation in VMEM scratch across the K sweep; the affine +
    relu epilogue runs on the accumulator before the single bf16 store
    — the HBM round trip XLA's separate BN/ReLU kernels would pay is
    gone (that's the whole experiment)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    nk = k // bk

    def kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, nk):
        kj = pl.program_id(2)

        @pl.when(kj == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.bfloat16),
            w_ref[...].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)

        @pl.when(kj == nk - 1)
        def _epilogue():
            y = acc_ref[...] * s_ref[...][0] + b_ref[...][0]
            o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)

    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, scale.reshape(1, -1), bias.reshape(1, -1))


def _paths(B, C, H, W, interpret=False):
    """(xla_fn, pallas_fn, inputs) for the SAME NCHW bottleneck stage."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    mx.random.seed(0)
    bf16 = "bfloat16"
    x = mx.random.uniform(shape=(B, C, H, W)).astype(bf16)
    w = mx.random.uniform(shape=(C, C, 1, 1)).astype(bf16)
    gamma = mx.random.uniform(shape=(C,)) + 0.5
    beta = mx.random.uniform(shape=(C,)) - 0.5
    mean = mx.random.uniform(shape=(C,)) * 0.1
    var = mx.random.uniform(shape=(C,)) + 0.9

    def xla_fn(x, w, gamma, beta, mean, var):
        y = nd.Convolution(x, w, kernel=(1, 1), num_filter=C,
                           no_bias=True)
        y = nd.BatchNorm(y, gamma, beta, mean, var,
                         use_global_stats=True)[0]
        return nd.relu(y)

    # BN folded to per-channel affine on the conv output
    def pallas_fn(x, w, gamma, beta, mean, var):
        from mxnet_tpu.ndarray import NDArray
        from mxnet_tpu.ops.registry import apply_op

        def f(xr, wr, g, b, mu, v):
            scale = (g / jnp.sqrt(v + 1e-5)).astype(jnp.float32)
            bias = (b - mu * scale).astype(jnp.float32)
            xm = xr.transpose(0, 2, 3, 1).reshape(-1, C)
            wm = wr.reshape(C, C).T
            ym = fused_matmul_affine_relu(xm, wm, scale, bias,
                                          interpret=interpret)
            return ym.reshape(xr.shape[0], xr.shape[2], xr.shape[3],
                              C).transpose(0, 3, 1, 2)

        return apply_op(f, x, w, gamma, beta, mean, var,
                        name="pallas_conv_bn_relu")

    return xla_fn, pallas_fn, [x, w, gamma, beta, mean, var]


def main():
    check = "--check" in sys.argv
    interpret = check

    B, C, H, W = ((4, 256, 16, 16) if check else (64, 256, 56, 56))
    xla_fn, pallas_fn, inputs = _paths(B, C, H, W, interpret=interpret)

    ref = xla_fn(*inputs).asnumpy().astype(np.float32)
    got = pallas_fn(*inputs).asnumpy().astype(np.float32)
    rms = float(np.sqrt(np.mean(ref.astype(np.float64) ** 2)))
    err = float(np.max(np.abs(ref - got)))
    # bf16 epilogue rounding: one ulp of the activation scale
    assert err <= max(0.02 * rms, 0.05), (err, rms)
    if check:
        print(json.dumps({"probe": "pallas_conv_bn_relu",
                          "numerics": "ok", "max_abs_err": err,
                          "interpret": interpret}))
        return

    from benchmark.opperf import _measure

    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    inner = int(os.environ.get("BENCH_OPPERF_INNER", "30"))
    flops = 2 * B * C * C * H * W
    t_xla = _measure(xla_fn, inputs, inner, repeats)
    t_pal = _measure(pallas_fn, inputs, inner, repeats)
    print(json.dumps({
        "probe": "pallas fused 1x1conv+BN+relu vs XLA chain "
                 "(PERF_NOTES ceiling question)",
        "shape": [B, C, H, W],
        "xla_usec_per_call": round(t_xla * 1e6, 2),
        "pallas_usec_per_call": round(t_pal * 1e6, 2),
        "xla_tflops": round(flops / t_xla / 1e12, 2),
        "pallas_tflops": round(flops / t_pal / 1e12, 2),
        "pallas_speedup": round(t_xla / t_pal, 3),
        "verdict": ("pallas wins — productionize in r5"
                    if t_pal < t_xla * 0.97 else
                    "no win — XLA's fusion already at the ceiling "
                    "(negative result, closes the question)"),
    }))


if __name__ == "__main__":
    main()

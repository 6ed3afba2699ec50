#!/usr/bin/env python
"""Per-op throughput harness (reference: ``benchmark/opperf/`` —
run-it-yourself per-op perf, SURVEY §6).

Times ~30 representative ops at training-relevant shapes on whatever
device jax boots (the chip by default).

Methodology — jitted ``lax.scan`` chains at two lengths, per-call time
from the slope (see ``_measure``): dispatch is asynchronous, so timing
eager ops one by one measures the enqueue, and a single scan's wall time
still carries the launch and the host fetch that closes it, so the
harness differences two scan lengths to cancel them.  Best of
``BENCH_REPEATS`` windows per length, same discipline as bench.py.

Emits ONE JSON object: ``{"ops": {name: {usec_per_call, gflops_per_sec?,
gbytes_per_sec?}}, ...}`` — future rounds diff this table to catch
op-level perf regressions that workload benches average away.

Run: ``python benchmark/opperf.py`` (chip) or
``BENCH_PLATFORM=cpu python benchmark/opperf.py`` (harness validation;
numbers meaningless).  ``BENCH_OPPERF_OUT=path`` writes the JSON there
too.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _grad_matmul_case(use_custom):
    """fn(a, b, g) -> loss + sum-of-grads for a 2048 matmul, either
    through the framework's dtype-preserving custom vjp (bf16 backward
    dots) or the naive dot(pet=f32).astype(bf16) pattern whose
    cotangents force f32xf32 backward dots (the r4 _mxu_matmul
    rationale).  FLOPs per call = 3x the forward (fwd + two bwd
    contractions).

    The r5 first cut of this row priced at 281 TF/s > 197 peak (caught
    by its own >peak audit rule): its loss was ``sum(y)``, so the
    cotangent was literally ones and XLA collapsed BOTH backward
    contractions (``ones @ b^T``/``a^T @ ones``) into reductions —
    2/3 of the assumed FLOPs never ran.  Now the loss is weighted by a
    full-rank random matrix ``g`` (cotangent = g, incompressible) and
    the grads pass an optimization_barrier before the digest sums, so
    ``sum(dy @ b^T)`` can't be rewritten as ``sum(dy) . sum(b)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def custom_fwd(ar, br):
        from mxnet_tpu.ops.nn_ops import mxu_matmul_nt

        return mxu_matmul_nt(ar, br)

    def pet_fwd(ar, br):
        return lax.dot_general(
            ar, br, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(ar.dtype)

    fwd = custom_fwd if use_custom else pet_fwd

    def fn(a, b, g):
        from mxnet_tpu.ops.registry import apply_op

        def f(ar, br, gr):
            def loss(ar_, br_):
                y = fwd(ar_, br_)
                return jnp.sum(y.astype(jnp.float32) *
                               gr.astype(jnp.float32))

            lv, (da, db) = jax.value_and_grad(
                loss, argnums=(0, 1))(ar, br)
            da, db = lax.optimization_barrier((da, db))
            return lv + jnp.sum(da.astype(jnp.float32)) + \
                jnp.sum(db.astype(jnp.float32))

        return apply_op(f, a, b, g, name="matmul_fwdbwd")

    return fn


def _cases(nd, mxr):
    """[(name, fn(*inputs)->NDArray, [inputs], flops, bytes_moved)] —
    flops use 1 MAC = 2."""
    f32 = "float32"
    bf16 = "bfloat16"

    def U(*s, dtype=f32):
        return mxr.uniform(shape=s).astype(dtype)

    B, C, H, W = 64, 256, 56, 56
    M = N = K = 2048
    T, NH, D = 2048, 16, 64

    x_conv = U(B, C, H, W, dtype=bf16)
    w3 = U(C, C, 3, 3, dtype=bf16)
    w1 = U(C, C, 1, 1, dtype=bf16)
    a_mm, b_mm = U(M, K, dtype=bf16), U(K, N, dtype=bf16)
    g_mm = U(M, N, dtype=bf16)  # full-rank cotangent for the fwdbwd A/B
    a32, b32 = U(M, K), U(K, N)
    big = U(64 * 1024 * 1024 // 4)  # 64 MB f32 vector
    x_bn, g = U(B, C, H, W), U(C)
    qkv = U(T, 4, 3 * NH * D, dtype=bf16)
    fc_x, fc_w = U(4096, 1024, dtype=bf16), U(1024, 1024, dtype=bf16)
    bd_a, bd_b = U(64, 512, 64, dtype=bf16), U(64, 64, 512, dtype=bf16)
    ln_x, ln_g, ln_b = U(8192, 768), U(768), U(768)
    att_q, att_k, att_v = (U(4, T, NH, D, dtype=bf16) for _ in range(3))
    rnn_x = U(128, 64, 512)
    rnn_h, rnn_c = U(1, 64, 512), U(1, 64, 512)
    rnn_w1, rnn_w2 = U(2048, 512), U(2048, 512)
    rnn_b1, rnn_b2 = U(2048), U(2048)
    emb_w = U(30522, 768)
    ids = nd.array((mxr.uniform(shape=(8192,)) * 30522).astype("int32"))
    x_sm = U(B * 16, 30522)
    la = U(512, 512)
    spd = nd.dot(la, la, transpose_b=True) + 512 * nd.eye(512)

    conv_flops = 2 * B * C * C * 3 * 3 * H * W
    qcx, qcx_mn, qcx_mx = nd.quantize_v2(x_conv.astype("float32"),
                                         out_type="int8")
    qcw, qcw_mn, qcw_mx = nd.quantize_v2(w3.astype("float32"),
                                         out_type="int8")
    qma, qma_mn, qma_mx = nd.quantize_v2(a32, out_type="int8")
    qmb, qmb_mn, qmb_mx = nd.quantize_v2(b32, out_type="int8")
    return [
        ("conv3x3_b64_c256_s56_bf16",
         lambda x, w: nd.Convolution(x, w, kernel=(3, 3), pad=(1, 1),
                                     num_filter=C, no_bias=True),
         [x_conv, w3], conv_flops, 0),
        ("conv1x1_b64_c256_s56_bf16",
         lambda x, w: nd.Convolution(x, w, kernel=(1, 1), num_filter=C,
                                     no_bias=True),
         [x_conv, w1], 2 * B * C * C * H * W, 0),
        ("matmul_2048_bf16", lambda a, b: nd.dot(a, b), [a_mm, b_mm],
         2 * M * N * K, 0),
        # int8 MXU rows (VERDICT r3 item 4): v5e's 2x int8 headline —
        # pre-quantized operands, the row measures the int8xint8->int32
        # contraction itself ("gflops" = int ops, 1 MAC = 2)
        ("quantized_conv3x3_b64_c256_s56_int8",
         lambda qx, qw, a1, a2, a3, a4: nd.quantized_conv(
             qx, qw, a1, a2, a3, a4, kernel=(3, 3), pad=(1, 1),
             num_filter=C, no_bias=True)[0],
         [qcx, qcw, qcx_mn, qcx_mx, qcw_mn, qcw_mx], conv_flops, 0),
        # fwd+bwd matmul pair: the framework's dtype-preserving custom
        # vjp (bf16 backward dots) vs the naive pet+astype reference
        # whose backward runs f32xf32 — the r4 fix's measured win
        ("matmul_fwdbwd_2048_bf16_customvjp",
         _grad_matmul_case(use_custom=True),
         [a_mm, b_mm, g_mm], 3 * 2 * M * N * K, 0),
        ("matmul_fwdbwd_2048_bf16_petref",
         _grad_matmul_case(use_custom=False),
         [a_mm, b_mm, g_mm], 3 * 2 * M * N * K, 0),
        ("quantized_matmul_2048_int8",
         lambda qa, qb, a1, a2, a3, a4: nd.quantized_fully_connected(
             qa, qb, a1, a2, a3, a4, num_hidden=N, no_bias=True,
             flatten=False)[0],
         [qma, qmb, qma_mn, qma_mx, qmb_mn, qmb_mx],
         2 * M * N * K, 0),
        ("matmul_2048_f32", lambda a, b: nd.dot(a, b), [a32, b32],
         2 * M * N * K, 0),
        ("fully_connected_4096x1024_bf16",
         lambda x, w: nd.FullyConnected(x, w, None, num_hidden=1024,
                                        no_bias=True),
         [fc_x, fc_w], 2 * 4096 * 1024 * 1024, 0),
        ("batch_dot_64x512x64_bf16",
         lambda a, b: nd.batch_dot(a, b), [bd_a, bd_b],
         2 * 64 * 512 * 64 * 512, 0),
        ("elemwise_add_64MB", lambda x: x + x, [big],
         0, 3 * big.size * 4),
        ("elemwise_mul_add_fused_64MB", lambda x: x * 1.5 + x, [big],
         0, 3 * big.size * 4),
        ("relu_64MB", lambda x: nd.relu(x), [big], 0, 2 * big.size * 4),
        ("tanh_64MB", lambda x: nd.tanh(x), [big], 0, 2 * big.size * 4),
        ("exp_64MB", lambda x: nd.exp(x), [big], 0, 2 * big.size * 4),
        ("sum_64MB", lambda x: nd.sum(x), [big], 0, big.size * 4),
        ("cumsum_64MB", lambda x: nd.cumsum(x), [big],
         0, 2 * big.size * 4),
        ("transpose_2048", lambda x: nd.transpose(x), [a32],
         0, 2 * M * K * 4),
        ("batch_norm_b64_c256_s56",
         lambda x, gg: nd.BatchNorm(x, gg, gg, gg, gg)[0], [x_bn, g],
         0, 2 * x_bn.size * 4),
        ("layer_norm_8192x768",
         lambda x, gg, bb: nd.LayerNorm(x, gg, bb), [ln_x, ln_g, ln_b],
         0, 2 * 8192 * 768 * 4),
        ("softmax_1024x30522",
         lambda x: nd.softmax(x, axis=-1), [x_sm], 0, 2 * x_sm.size * 4),
        ("log_softmax_1024x30522",
         lambda x: nd.log_softmax(x, axis=-1), [x_sm],
         0, 2 * x_sm.size * 4),
        ("maxpool_2x2_b64_c256_s56",
         lambda x: nd.Pooling(x, kernel=(2, 2), stride=(2, 2),
                              pool_type="max"), [x_bn],
         0, 1.25 * x_bn.size * 4),
        ("embedding_8192_of_30522x768",
         lambda i, w: nd.embedding(i, w, input_dim=30522,
                                   output_dim=768), [ids, emb_w],
         0, 8192 * 768 * 4),
        ("take_8192_rows", lambda i, w: nd.take(w, i, axis=0),
         [ids, emb_w], 0, 8192 * 768 * 4),
        ("one_hot_8192x1024",
         lambda i, w: nd.one_hot(i, depth=1024) * w[0, 0],
         [ids, emb_w], 0, 8192 * 1024 * 4),
        ("topk_64x30522_k5",
         lambda x: nd.topk(x, k=5, ret_typ="value", axis=-1),
         [nd.slice_axis(x_sm, axis=0, begin=0, end=64)],
         0, 64 * 30522 * 4),
        ("sort_1M",
         lambda x: nd.sort(x),
         [nd.slice_axis(big, axis=0, begin=0, end=2 ** 20)],
         0, 2 * 2 ** 20 * 4),
        ("argmax_1024x30522",
         lambda x: nd.argmax(x, axis=-1) * 1.0, [x_sm],
         0, x_sm.size * 4),
        ("interleaved_selfatt_qk_t2048_h16",
         lambda q: nd.interleaved_matmul_selfatt_qk(q, heads=NH), [qkv],
         2 * 4 * NH * T * T * D, 0),
        ("flash_attention_t2048_h16",
         lambda q, k, v: nd.dot_product_attention(q, k, v),
         [att_q, att_k, att_v], 4 * 4 * NH * T * T * D, 0),
        ("lstm_fused_t128_b64_h512",
         lambda x, h, c, w1_, w2_, b1_, b2_: nd.rnn(
             x, [h, c], [w1_, w2_, b1_, b2_], mode="lstm",
             state_size=512, num_layers=1)[0],
         [rnn_x, rnn_h, rnn_c, rnn_w1, rnn_w2, rnn_b1, rnn_b2],
         2 * 128 * 64 * (512 * 2048 * 2), 0),
        ("linalg_potrf_512", lambda a: nd.linalg_potrf(a), [spd],
         512 ** 3 / 3, 0),
        ("linalg_trsm_512", lambda lo, b: nd.linalg_trsm(lo, b),
         [nd.linalg_potrf(spd), la], 512 ** 3, 0),
        ("where_64MB", lambda x: nd.where(x > 0.5, x, -x), [big],
         0, 3 * big.size * 4),
        ("cast_bf16_64MB", lambda x: nd.cast(x, bf16) * 1.0, [big],
         0, 1.5 * big.size * 4),
    ]


def _measure(fn, inputs, inner, repeats):
    """Device time per call of ``fn``.

    Two fences: (1) completion is forced by fetching a scalar reduction
    of the result (device→host of 4 bytes) — a host fetch cannot return
    before the work is done; (2) that fetch and the launch are fixed
    costs that would swamp a single small program, so the op runs as a
    jitted ``lax.scan`` of serially-dependent iterations at TWO lengths
    and the per-call time is the slope ``(t(4k) - t(k)) / 3k`` — the
    fixed costs cancel.  The scan carry threads an
    output-dependent ~1e-32 perturbation into the first float input, so
    iterations can't overlap, fold, or dead-code-eliminate.  The
    per-iteration ``sum(out)`` dependency adds one output read pass —
    bandwidth figures include it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ndarray import NDArray

    raws = tuple(a._data for a in inputs)
    float_i = next(i for i, r in enumerate(raws)
                   if jnp.issubdtype(r.dtype, jnp.floating))

    def body(carry, _):
        outs = fn(*[NDArray(c) for c in carry])
        out0 = outs[0] if isinstance(outs, (list, tuple)) else outs
        # optimization_barrier forces the output to MATERIALIZE (else
        # XLA folds linear ops into scalar recurrences across the chain
        # — measured zero marginal cost for add/transpose/layer_norm)
        # and stops cross-iteration algebraic rewrites of the digest
        out_b = lax.optimization_barrier(out0._data)
        s = jnp.sum(out_b.astype(jnp.float32))
        eps = (s * jnp.float32(1e-32)).astype(carry[float_i].dtype)
        carry = tuple(c + eps if i == float_i else c
                      for i, c in enumerate(carry))
        return lax.optimization_barrier(carry), None

    def timed(n):
        jfn = jax.jit(lambda c: jnp.sum(
            lax.scan(body, c, None, length=n)[0][float_i]
            .astype(jnp.float32)))
        float(jfn(raws))  # compile + warm (fetch forces completion)
        best = float("inf")
        for _ in range(repeats):
            tic = time.time()
            float(jfn(raws))
            best = min(best, time.time() - tic)
        return best

    n1, n2 = inner, 4 * inner
    per = (timed(n2) - timed(n1)) / (n2 - n1)
    return max(per, 1e-9)


def main():
    plat = os.environ.get("BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    mx.random.seed(0)
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    inner = int(os.environ.get("BENCH_OPPERF_INNER", "50"))

    # substring filter for quick reruns / CPU harness validation (the
    # full MXU-sized shapes are hours on a 1-core host)
    filt = os.environ.get("BENCH_OPPERF_FILTER", "")
    table = {}
    for name, fn, inputs, flops, nbytes in _cases(nd, mx.random):
        if filt and filt not in name:
            continue
        # adaptive chain length (VERDICT r3 weak 3): if the slope
        # vanishes into RTT jitter at this length, the per-op cost is
        # below the floor — QUADRUPLE the chain until the aggregate
        # delta dominates the noise (caps at 64x so a genuinely-free op
        # can't spin forever)
        inner_n = inner
        best = _measure(fn, inputs, inner_n, repeats)
        while best <= 2e-9 and inner_n < inner * 64:
            inner_n *= 4
            best = _measure(fn, inputs, inner_n, repeats)
        row = {"usec_per_call": round(best * 1e6, 2)}
        if inner_n != inner:
            row["chain_len"] = inner_n
        if best <= 2e-9:
            # still unresolved at the longest chain — flag honestly
            row["below_noise_floor"] = True
        if flops:
            row["gflops_per_sec"] = round(flops / best / 1e9, 1)
        if nbytes:
            row["gbytes_per_sec"] = round(nbytes / best / 1e9, 1)
        table[name] = row

    result = {
        "harness": "benchmark/opperf.py",
        "platform": str(jax.devices()[0]),
        "aggregation": f"slope_of_chained_scans_len_{inner}_vs_"
                       f"{4 * inner}_best_of_{repeats}",
        "time": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "ops": table,
    }
    blob = json.dumps(result, indent=1, sort_keys=True)
    print(blob)
    out_path = os.environ.get("BENCH_OPPERF_OUT")
    if out_path:
        with open(out_path, "w") as f:
            f.write(blob + "\n")


if __name__ == "__main__":
    main()

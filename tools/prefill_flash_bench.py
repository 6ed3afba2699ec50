#!/usr/bin/env python
"""The served prefill's flash forward kernel alone, on the chip: TFLOP/s
of the causal half at 32 query / 8 KV heads over tile sizes and prompt
buckets, against ``ops.attention.masked_attention`` at the same shapes,
and the two-layer prefill program a bucket, kernel against dense path.

    chiprun -- python tools/prefill_flash_bench.py [--quick]
    chiprun -- python tools/prefill_flash_bench.py --train 128,12,128,64

``--train B,H,T,D`` times the trainer's three kernels instead (forward,
dq, dkv, and the whole custom-vjp with the XLA around it) by the rows a
grid step takes (``ops.flash_attention.train_tiles``); with ``--tiles``
each kernel alone at each of those tiles, the other two at the rule's
(``ops.flash_attention.train_blocks``), and the rule's own choice.
``--layout heads`` (the default) times the head-major kernels on
``(B, H, T, D)`` and, beside them, the whole vjp from token-major
operands with the eight transposes ``ops.attention.sdpa_raw`` puts around
it (``vjp_transposed_ms``; in this chain XLA folds them into its own
fusions, 2.056 beside ``vjp_ms`` 2.148 at BERT's shape, PERF.md, PR 50:
what the copies cost a model is its trace's ``copy`` rows to say);
``--layout tokens`` the token-major kernels on ``(B, T, H x D)``
(``ops.flash_attention.flash_attention_tokens``; rows a step by
``tokens_rows``).

Each timing is one jitted program of ``CALLS`` chained calls (the output
feeds the next call's queries, as a decoder's layers do), run ``REPS``
times after a warm-up; the median over REPS, over CALLS.  Operations
counted: QK^T and PV over the ``n (n + 1) / 2`` pairs a causal prompt of
``n`` tokens needs (``chipbench/flops_bytes/llama_prefill.py``'s count).
Lines of JSON to stdout and ``chiprun_out/prefill_flash_bench.jsonl``.
There is no CPU mode: a CPU time is no reading of the kernel.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

CALLS, REPS = 8, 7
H, HKV = 32, 8
#: (block_q, block_k) tried at 4 query heads a KV head of 128; 512 x 1024
#: and wider need more VMEM than a kernel gets by default
SWEEP = [(128, 128), (128, 512), (256, 256), (256, 512), (512, 256),
         (512, 512), (128, 1024), (256, 1024), (128, 2048), (256, 2048),
         (128, 4096)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="the chosen tiles only, no sweep")
    ap.add_argument("--tiles",
                    help="block_q:block_k pairs of the sweep (default: "
                         "the served prefill's at heads of 128); with "
                         "--train, of each training kernel in turn")
    ap.add_argument("--vmem-mib", type=int,
                    help="with --train --tiles: the kernels' "
                         "vmem_limit_bytes raised to this (the rule "
                         "stays inside the default)")
    ap.add_argument("--kernel-only", action="store_true",
                    help="skip the two-layer prefill programs")
    ap.add_argument("--train", metavar="B,H,T,D[,Dv]",
                    help="the training kernels alone at this shape "
                         "(forward, dq, dkv), by rows a grid step; "
                         "nothing of the prefill.  Dv: v's own width "
                         "(latent attention's expanded heads: 192,128)")
    ap.add_argument("--causal", action="store_true",
                    help="the --train kernels under the causal mask")
    ap.add_argument("--layout", choices=("heads", "tokens"), default="heads",
                    help="the --train kernels' entry: head-major (B, H, T, "
                         "D), the transposes around it timed beside it, or "
                         "token-major (B, T, H x D)")
    ap.add_argument("--rows", default="1,4,8,12,16,24",
                    help="rows a grid step of the --train sweep; the "
                         "rule's own choice is always timed")
    args = ap.parse_args()
    sweep = [tuple(int(n) for n in t.split(":"))
             for t in args.tiles.split(",")] if args.tiles else SWEEP

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import masked_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("prefill_flash_bench measures the chip: no TPU")
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/prefill_flash_bench.jsonl", "a")

    def say(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def timed(fn, *operands, calls=CALLS):
        """Median seconds of one of the ``calls`` chained in ``fn``."""
        out = fn(*operands)
        jax.block_until_ready(out)
        took = []
        for _ in range(REPS):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            took.append(time.perf_counter() - t)
        return statistics.median(took) / calls

    def chain(attend):
        def run(q, k, v, n):
            for _ in range(CALLS):
                q = attend(q, k, v, n)
            return q
        return jax.jit(run)

    def operands(hd, lp, hkv=HKV):
        keys = jax.random.split(jax.random.PRNGKey(lp + hd), 3)
        return (jax.random.normal(keys[0], (1, H, lp, hd), jnp.bfloat16),
                jax.random.normal(keys[1], (1, hkv, lp, hd), jnp.bfloat16),
                jax.random.normal(keys[2], (1, hkv, lp, hd), jnp.bfloat16))

    def flops(hd, n):
        return 4 * H * hd * (n * (n + 1) // 2)

    if args.train:
        train(args, fa, say, timed, sweep if args.tiles else None)
        return

    def kernel(bq, bk, hd):
        return chain(lambda q, k, v, n: fa._fa_forward_pallas(
            q, k, v, True, 1.0 / float(np.sqrt(hd)), bq, bk, lengths=n,
            name="prefill_flash_attention"))

    # -- the kernel alone ---------------------------------------------------
    for hd in (128, 64):
        for lp in (4096, 2048, 1024, 512, 256, 128):
            q, k, v = operands(hd, lp)
            full = jnp.full((1,), lp, jnp.int32)
            mask = jnp.tril(jnp.ones((lp, lp), bool))
            dense = timed(chain(lambda q, k, v, n: masked_attention(
                q, k, v, mask)), q, k, v, full)
            say(what="dense", hd=hd, lp=lp, ms=dense * 1e3,
                tflops=flops(hd, lp) / dense / 1e12)
            chosen = fa.prefill_tiles(H // HKV, lp)
            tiles = [chosen] if args.quick or hd == 64 or lp < 512 else \
                sorted({(min(a, lp), min(b, lp)) for a, b in sweep}
                       | {chosen})
            for bq, bk in tiles:
                try:
                    took = timed(kernel(bq, bk, hd), q, k, v, full)
                except Exception as e:      # a tile Mosaic refuses
                    say(what="kernel", hd=hd, lp=lp, bq=bq, bk=bk,
                        error=str(e)[:200])
                    continue
                say(what="kernel", hd=hd, lp=lp, bq=bq, bk=bk,
                    chosen=(bq, bk) == chosen, ms=took * 1e3,
                    tflops=flops(hd, lp) / took / 1e12)
            # the p90 prompt of mistral7b.doc_prefill fills 63% of its
            # bucket: what skipping the tiles past the true length buys
            n63 = int(lp * 0.63)
            bq, bk = chosen
            took = timed(kernel(bq, bk, hd), q, k, v,
                         jnp.full((1,), n63, jnp.int32))
            say(what="kernel_len63", hd=hd, lp=lp, bq=bq, bk=bk,
                ms=took * 1e3, tflops=flops(hd, n63) / took / 1e12)
            if hd == 128 and lp >= 512 and not args.quick:
                # a K/V index map h -> h // G in place of the group in
                # the tile reads K and V once a query head: the same
                # bytes as this call on repeated K/V, a head a grid row
                kr, vr = (jnp.repeat(a, H // HKV, axis=1) for a in (k, v))
                for bq, bk in ((512, 512), (256, 512), (512, 1024)):
                    bq, bk = min(bq, lp), min(bk, lp)
                    took = timed(kernel(bq, bk, hd), q, kr, vr, full)
                    say(what="kernel_head_a_row", hd=hd, lp=lp, bq=bq,
                        bk=bk, ms=took * 1e3,
                        tflops=flops(hd, lp) / took / 1e12)

    if args.kernel_only:
        return
    # -- the prefill program, two layers at Mistral-7B's widths -------------
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import (LlamaConfig, LlamaDecoder,
                                        LlamaForCausalLM)

    mx.random.seed(3)
    net = LlamaForCausalLM(LlamaConfig(
        hidden_size=4096, intermediate_size=14336, num_layers=2,
        num_heads=H, num_kv_heads=HKV, vocab_size=32768, max_seq_len=4096,
        rope_theta=1e6, tie_embeddings=False))
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    dec = LlamaDecoder(net, max_len=4096)
    w = dec._weights()
    prog = jax.jit(lambda w, ids, t0, flash: dec._prefill_rows_impl(
        w, ids, t0, flash)[1], static_argnums=3)
    for lp in (32, 64, 128, 256, 512, 1024, 2048, 4096):
        ids = jnp.ones((1, lp), jnp.int32)
        t0 = jnp.full((1,), lp, jnp.int32)
        rec = {"what": "program_2_layers", "lp": lp}
        for flash in (False, True):
            if flash and lp % 128:
                continue
            rec["flash_ms" if flash else "dense_ms"] = \
                timed(prog, w, ids, t0, flash, calls=1) * 1e3
        say(**rec)


def train(args, fa, say, timed, tiles=None):
    """Forward, dq and dkv at one (B, H, T, D) in bf16 (``v``, ``o`` and
    ``do`` Dv wide where a fifth number is given), non-causal as BERT
    runs them or ``--causal``: each a program of ``CALLS`` chained calls
    (the result feeds the next call's q, or k and v), by rows a grid step
    (``train_tiles`` patched, as tier 1 patches it).  A program that
    returns dq alone holds no dkv call and the other way round (XLA
    drops a kernel whose results nothing reads); the head-major
    ``delta`` is computed once a program, its operands being the same in
    every call (the token-major kernels take it themselves).  ``--layout``:
    which entry, see the module's docstring."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    b, h, t, d, *rest = (int(n) for n in args.train.split(","))
    dv, causal = (rest[0] if rest else d), bool(args.causal)
    tokens = args.layout == "tokens"
    scale = 1.0 / float(np.sqrt(d))
    keys = jax.random.split(jax.random.PRNGKey(t + d), 4)
    q, k, v, do = (jax.random.normal(kk, (b, h, t, w), jnp.bfloat16)
                   for kk, w in zip(keys, (d, d, dv, dv)))

    def flat(x):        # (B, H, T, D) -> (B, T, H x D), as projections lie
        return x.transpose(0, 2, 1, 3).reshape(b, t, -1)

    if tokens:
        if dv != d or tiles:
            raise SystemExit("--layout tokens: one width, one tile")
        pair = fa.tokens_lanes(d)[1]
        q, k, v, do = (flat(x) for x in (q, k, v, do))
        rule, patched = fa.tokens_rows, "tokens_rows"
        chosen = rule(b, pair, t, t, d) * pair

        def forward(q, k, v):
            return fa._fa_forward_tokens(q, k, v, h, causal, scale,
                                         with_lse=True)

        def backward(q, k, v, o, do, lse):
            return fa._fa_backward_tokens(q, k, v, o, do, lse, h, causal,
                                          scale)

        def attend(a, b_, c):
            return fa.flash_attention_tokens(a, b_, c, h, causal, scale)
    else:
        pair = 1
        rule, patched = fa.train_tiles, "train_tiles"
        chosen = rule(b * h, t, t, max(d, dv))

        def forward(q, k, v):
            return fa._fa_forward_pallas(q, k, v, causal, scale,
                                         with_lse=True)

        def backward(q, k, v, o, do, lse):
            return fa._fa_backward_pallas(q, k, v, o, do, lse, causal, scale)

        def attend(a, b_, c):
            return fa.flash_attention_raw(a, b_, c, causal, scale)

    o, lse = jax.jit(forward)(q, k, v)
    if dv != d:
        # the chain feeds a result back as the next call's operand: at two
        # widths a call's results are summed into a scalar nudge instead
        def nudge(x, *results):
            return x + sum(r.astype(jnp.float32).mean() for r in results) \
                .astype(x.dtype) * 0
    else:
        nudge = None

    def fwd(q, k, v, o, do, lse):
        for _ in range(CALLS):
            out = forward(q, k, v)[0]
            q = nudge(q, out) if nudge else out
        return q

    def dq(q, k, v, o, do, lse):
        for _ in range(CALLS):
            q = backward(q, k, v, o, do, lse)[0]
        return q

    def dkv(q, k, v, o, do, lse):
        for _ in range(CALLS):
            _, k, v = backward(q, k, v, o, do, lse)
        return k, v

    def vjp(q, k, v, o, do, lse, attend=attend):
        for _ in range(CALLS):
            out, pull = jax.vjp(attend, q, k, v)
            q, k, v = pull(do)
            q = nudge(q, out) if nudge else q + out
        return q, k, v

    def heads_from_tokens(a, b_, c):
        """``sdpa_raw``'s head-major branch on token-major operands."""
        def tr(x):
            return x.reshape(b, t, h, -1).transpose(0, 2, 1, 3)
        return flat(attend(tr(a), tr(b_), tr(c)))

    operands = (q, k, v, o, do, lse)
    programs = [("fwd", fwd, operands), ("dq", dq, operands),
                ("dkv", dkv, operands), ("vjp", vjp, operands)]
    if not tokens and dv == d:
        programs.append((
            "vjp_transposed",
            functools.partial(vjp, attend=heads_from_tokens),
            tuple(x if x is lse else jax.jit(flat)(x) for x in operands)))

    if tiles:
        train_by_tiles(fa, say, timed, tiles, args.vmem_mib,
                       (b, h, t, d, dv), causal,
                       {"fwd": fwd, "dq": dq, "dkv": dkv}, operands)
        return
    rows = sorted({int(r) for r in args.rows.split(",") if r} | {chosen})
    try:
        for hb in rows:
            if (b * h) % hb or hb % pair or b % (hb // pair):
                continue
            setattr(fa, patched, lambda *_: hb // pair)
            rec = {"what": "train", "shape": [b, h, t, d], "dv": dv,
                   "causal": causal, "layout": args.layout, "hb": hb,
                   "chosen": hb == chosen}
            for name, fn, given in programs:
                try:
                    rec[name + "_ms"] = round(timed(
                        jax.jit(lambda *a, fn=fn: fn(*a)), *given)
                        * 1e3, 4)
                except Exception as e:      # a step Mosaic refuses
                    rec[name + "_error"] = str(e)[-300:]
            if all(n + "_ms" in rec for n in ("fwd", "dq", "dkv")):
                rec["layer_ms"] = round(
                    rec["fwd_ms"] + rec["dq_ms"] + rec["dkv_ms"], 4)
                rec["step_us"] = {n: round(rec[n + "_ms"] * 1e3 * hb
                                           / (b * h), 3)
                                  for n in ("fwd", "dq", "dkv")}
            say(**rec)
    finally:
        setattr(fa, patched, rule)


def train_by_tiles(fa, say, timed, tiles, vmem_mib, shape, causal, programs,
                   operands):
    """Each training kernel alone at each of ``tiles`` and at the rule's
    own (``train_blocks`` patched for that kernel, as the rows a step are
    above): ms a layer-call, the table of the rule's docstring.
    ``vmem_mib`` raises every kernel's ``vmem_limit_bytes``."""
    import functools

    import jax
    from jax.experimental.pallas import tpu as pltpu

    b, h, t, d, dv = shape
    rule, params = fa.train_blocks, pltpu.CompilerParams
    if vmem_mib:
        pltpu.CompilerParams = functools.partial(
            params, vmem_limit_bytes=vmem_mib << 20)
    try:
        for kernel, fn in programs.items():
            chosen = rule(kernel, t, t, d, dv, 2, causal)
            for bq, bk in [chosen] + [p for p in tiles if p != chosen]:
                if t % bq or t % bk:
                    continue
                fa.train_blocks = lambda kern, *a, **kw: \
                    (bq, bk) if kern == kernel else rule(kern, *a, **kw)
                rec = {"what": "train_tiles", "shape": [b, h, t, d],
                       "dv": dv, "causal": causal, "kernel": kernel,
                       "bq": bq, "bk": bk, "chosen": (bq, bk) == chosen,
                       "vmem_mib": vmem_mib}
                try:
                    rec["ms"] = round(timed(
                        jax.jit(lambda *a, fn=fn: fn(*a)), *operands)
                        * 1e3, 4)
                except Exception as e:      # tiles Mosaic refuses
                    rec["error"] = str(e)[-300:]
                say(**rec)
    finally:
        fa.train_blocks, pltpu.CompilerParams = rule, params


if __name__ == "__main__":
    main()

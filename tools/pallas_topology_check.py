#!/usr/bin/env python
"""AOT-compile every pallas kernel in the framework for a REAL v5e
target via the offline libtpu topology client (no chips).

Purpose: de-risk the on-chip lane.  A mosaic lowering error would
otherwise only surface when real chip time is available (and burn it).
Each kernel must compile to TPU HLO (asserted via the TPU-only tiled
layouts) carrying a mosaic custom-call.  XLA's estimated_cycles for its
own reference implementation of the same computation is recorded where
available as the bar the kernel has to beat on chip (custom-calls carry
no XLA cycle estimate — timing is the runbook's job).

Kernels covered:
- flash-attention forward (ops/flash_attention._fa_forward_pallas)
- fused matmul+affine+ReLU conv probe
  (tools/pallas_conv_probe.fused_matmul_affine_relu)
- paged decode attention (ops/paged_attention.paged_decode_attention),
  also compiled by tests/test_paged_attention.py

Writes one JSON blob to stdout (and argv[1] if given).  Single-process
(libtpu lockfile).
"""
import json
import re
import sys


def main():
    import os

    os.environ.pop("JAX_PLATFORMS", None)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _tpu_topology import (assert_tpu_hlo, compile_tpu_checked,
                               count_mosaic_calls, topology_mesh)

    mesh = topology_mesh("v5e:1x1")

    out = {"topology": "v5e:1x1 (offline libtpu AOT client)",
           "kernels": {}}

    def record(name, fn, avals, ref_fn=None):
        try:
            _comp, hlo = compile_tpu_checked(fn, avals, mesh, what=name)
            mosaic = count_mosaic_calls(hlo)
            # compiling without a mosaic kernel means the pallas path
            # silently degraded — that's a failure for a DE-RISK tool
            rec = {
                "tpu_compile_ok": mosaic > 0,
                "mosaic_custom_calls": mosaic,
            }
            if mosaic == 0:
                rec["error"] = "compiled but no tpu_custom_call in HLO"
        except Exception as e:
            rec = {"tpu_compile_ok": False,
                   "error": f"{type(e).__name__}: {e}"[:400]}
        if ref_fn is not None:
            try:
                _rc, rhlo = compile_tpu_checked(ref_fn, avals, mesh,
                                                what=name + "_ref")
                cyc = [int(c) for c in re.findall(
                    r'"estimated_cycles":"(\d+)"', rhlo)]
                rec["xla_reference_estimated_cycles_sum"] = sum(cyc)
            except Exception as e:
                rec["xla_reference_error"] = str(e)[:200]
        out["kernels"][name] = rec

    # flash attention forward, llama-8B head geometry at T=2048
    from mxnet_tpu.ops import flash_attention as fa

    B, H, T, D = 1, 8, 2048, 128
    qkv = [jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)] * 3
    scale = 1 / float(np.sqrt(D))
    record("flash_attention_fwd_bf16_T2048",
           lambda q, k, v: fa._fa_forward_pallas(q, k, v, True, scale),
           qkv,
           ref_fn=lambda q, k, v: fa._sdpa_ref(q, k, v, True, scale))

    # flash backward: the two-kernel dq/dkv design (forward saves lse)
    lse_aval = jax.ShapeDtypeStruct((B, H, T), jnp.float32)
    record("flash_attention_bwd_bf16_T2048",
           lambda q, k, v, o, g, lse: fa._fa_backward_pallas(
               q, k, v, o, g, lse, True, scale),
           qkv + [jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)] * 2
           + [lse_aval])

    # paged decode attention at the serving cell's shapes: 64 slots of
    # 1024 tokens over a pool of 4096 blocks of 16, 32/8 heads of 128
    from mxnet_tpu.ops.paged_attention import paged_decode_attention

    pool_aval = jax.ShapeDtypeStruct((4096, 8, 16, 128), jnp.bfloat16)
    record("paged_decode_attention_bf16_64x1024",
           paged_decode_attention,
           [jax.ShapeDtypeStruct((64, 32, 128), jnp.bfloat16),
            pool_aval, pool_aval,
            jax.ShapeDtypeStruct((64, 64), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.int32)])

    # fused 1x1conv(matmul)+BN-affine+ReLU probe kernel
    from pallas_conv_probe import fused_matmul_affine_relu

    M, K, N = 4096, 256, 512  # 64x64 spatial x 256ch -> 512ch 1x1 conv
    avals = [jax.ShapeDtypeStruct((M, K), jnp.bfloat16),
             jax.ShapeDtypeStruct((K, N), jnp.bfloat16),
             jax.ShapeDtypeStruct((N,), jnp.float32),
             jax.ShapeDtypeStruct((N,), jnp.float32)]

    def xla_ref(x, w, s, b):
        y = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return jnp.maximum(y * s + b, 0.0).astype(x.dtype)

    record("fused_matmul_affine_relu_bf16",
           fused_matmul_affine_relu, avals, ref_fn=xla_ref)

    # sequence-parallel routes on a 4-chip sp mesh, flash forced on:
    # - ULYSSES reaches the flash kernel INSIDE the shard_map body
    #   (sdpa_raw after the head/seq all-to-all) — the exact scenario
    #   whose nested-shard_map ValueError round-5 review repro'd
    #   pre-fix, so a mosaic call is REQUIRED here;
    # - RING never engages the kernel by design (its per-rotation
    #   online-softmax einsum body IS the attention), so its entry is
    #   compile-success + collective-permute count only.
    os.environ["MXT_FORCE_PALLAS_FLASH"] = "1"
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel import mesh_scope
    from mxnet_tpu.parallel.ring import (ring_attention_raw,
                                         ulysses_attention_raw)

    sp_mesh = topology_mesh("v5e:2x2", {"sp": 4})
    # *_attention_raw take the llama head layout (B, H, T, D) and
    # shard T over the ring internally
    sp_shard = NamedSharding(sp_mesh, P(None, None, "sp", None))
    B, N, T, H = 1, 8, 2048, 128

    def sp_case(name, fn, mosaic_required, collective):
        """``collective``: (hlo_opcode, min_count) that PROVES the
        sequence-parallel route engaged — a silent fallback (axis
        rename, spec drift) otherwise compiles fine with zero
        collectives and would record a vacuous pass."""
        try:
            with mesh_scope(sp_mesh):
                shaped = [jax.ShapeDtypeStruct(
                    (B, N, T, H), jnp.bfloat16,
                    sharding=sp_shard)] * 3
                comp = jax.jit(fn).lower(*shaped).compile()
            hlo = comp.as_text()
            assert_tpu_hlo(hlo, what=name)
            mosaic = count_mosaic_calls(hlo)
            # count instruction DEFINITIONS (one per op; async pairs
            # count the -start only; async ops have TUPLE types with
            # spaces between '=' and the opcode) — a bare substring
            # count would also hit every USE of an %all-to-all.N name
            counts = {
                op: len(re.findall(
                    rf"= .* {op}(?:-start)?\(", hlo))
                for op in ("collective-permute", "all-to-all")}
            op, need = collective
            ok = counts[op] >= need and \
                (mosaic > 0 if mosaic_required else True)
            rec = {
                "tpu_compile_ok": ok,
                "mosaic_custom_calls": mosaic,
                "collective_permutes": counts["collective-permute"],
                "all_to_alls": counts["all-to-all"],
            }
            if not ok:
                rec["error"] = (
                    f"compiled but route degraded: {counts[op]} "
                    f"{op} (need >= {need}), {mosaic} mosaic calls"
                    f" (required: {mosaic_required})")
        except Exception as e:
            rec = {"tpu_compile_ok": False,
                   "error": f"{type(e).__name__}: {e}"[:400]}
        out["kernels"][name] = rec

    sp_case("ulysses_attention_sp4_flash",
            lambda q, k, v: ulysses_attention_raw(
                q, k, v, causal=True, mesh=sp_mesh),
            mosaic_required=True, collective=("all-to-all", 4))
    sp_case("ring_attention_sp4",
            lambda q, k, v: ring_attention_raw(
                q, k, v, causal=True, mesh=sp_mesh),
            mosaic_required=False,
            collective=("collective-permute", 2))

    # multi-axis mesh: operand vma ({'sp'} or {'dp','sp'}) is a strict
    # subset story — the kernel's out_shape must declare the OPERANDS'
    # axes, not all manual axes (review-caught over-claim)
    sp_mesh = topology_mesh("v5e:2x4", {"dp": 2, "sp": 4})
    sp_shard = NamedSharding(sp_mesh, P("dp", None, "sp", None))
    B = 2
    sp_case("ulysses_attention_dp2xsp4_flash",
            lambda q, k, v: ulysses_attention_raw(
                q, k, v, causal=True, mesh=sp_mesh),
            mosaic_required=True, collective=("all-to-all", 4))

    # the full sequence-parallel TRAIN direction: value_and_grad of the
    # ulysses loss — the flash custom-vjp backward (two mosaic kernels)
    # runs INSIDE the shard_map body, a2a count doubles (fwd q/k/v/out
    # + bwd cotangent trades)
    sp_mesh = topology_mesh("v5e:2x2", {"sp": 4})
    sp_shard = NamedSharding(sp_mesh, P(None, None, "sp", None))
    B = 1  # sp_case reads the geometry globals at call time — restore
    # the sp4 forward case's shapes so the recorded numbers compare

    def ulysses_loss_grad(q, k, v):
        return jax.value_and_grad(
            lambda a, b, c: (ulysses_attention_raw(
                a, b, c, causal=True, mesh=sp_mesh)
                .astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

    sp_case("ulysses_sp4_value_and_grad_flash", ulysses_loss_grad,
            mosaic_required=True, collective=("all-to-all", 8))

    blob = json.dumps(out, indent=1)
    print(blob)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(blob + "\n")
    if not all(k["tpu_compile_ok"] for k in out["kernels"].values()):
        sys.exit(1)


if __name__ == "__main__":
    main()

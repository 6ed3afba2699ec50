#!/usr/bin/env python
"""Does XLA:TPU engage an int8 MXU path? (VERDICT r4 #6, HLO-evidence
half — the throughput half needs the live chip and lives in
benchmark/opperf.py int8 rows.)

Compiles int8xint8->int32 matmul and conv against an OFFLINE libtpu
v5e topology client (no chip needed).  CRITICAL mechanics: every aval
must carry a sharding over the TOPOLOGY's devices — bare avals compile
against the process's default CPU backend and the "TPU evidence" would
silently be CPU HLO (caught by review in r5).  TPU provenance is
asserted via the TPU-only tiled layouts (``{...:T(8,128)...}``) in the
optimized HLO.

Verdict signals, per case:
- ``native_s8_contraction``: an s32-output dot/convolution exists AND
  no ``convert`` widens an s8 operand anywhere in the module (on TPU
  the int8 matmul lowers to ``s32 convolution(s8, s8)`` through pure
  bitcast fusions, with the int8-packed ``T(8,128)(4,1)`` layout — 4
  bytes per 32-bit word);
- ``estimated_cycles``: XLA:TPU's own cost estimate from the fusion
  backend_config — comparing the int8 case against the bf16 control of
  the SAME shape shows whether the compiler prices int8 faster;
- the contraction HLO lines themselves, for the artifact.

Writes one JSON blob to stdout (and to argv[1] if given).
Single-process: libtpu holds a /tmp lockfile — don't run concurrently
with tools/scale_proof.py SP_BACKEND=tpu.
"""
import json
import re
import sys


def _dot_lines(hlo):
    keep = []
    for ln in hlo.splitlines():
        s = ln.strip()
        if re.search(r"= \S+ (dot|convolution)\(", s) or \
                re.search(r"= \S+ convert\(", s):
            keep.append(s[:200])
    return keep


def main():
    import os

    os.environ.pop("JAX_PLATFORMS", None)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax import lax

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _tpu_topology import compile_tpu_checked, topology_mesh

    mesh = topology_mesh("v5e:1x1")

    out = {"topology": "v5e:1x1 (offline libtpu AOT client)",
           "cases": {}}

    def probe(name, fn, *avals):
        comp, hlo = compile_tpu_checked(fn, avals, mesh, what=name)
        # keyed by FULL instruction name: stripping the .N suffix would
        # collapse same-named defs of different dtypes (%fusion.1 s32
        # vs %fusion.2 s8) and let the widening scan resolve a
        # convert's operand to the wrong dtype
        defs = dict(re.findall(r"%([\w.\-]+) = (\w+)\[", hlo))
        has_s32_contraction = bool(re.search(
            r"= s32\[[^\]]*\]\S* (?:dot|convolution)\(", hlo))
        # any convert that WIDENS an s8 value disqualifies nativeness
        widening_convert = False
        for m in re.finditer(
                r"= (\w+)\[[^\]]*\]\S* convert\(%([\w.\-]+)\)", hlo):
            to_t, op = m.group(1), m.group(2)
            if defs.get(op) == "s8" and to_t != "s8":
                widening_convert = True
        cycles = [int(c) for c in
                  re.findall(r'"estimated_cycles":"(\d+)"', hlo)]
        ca = comp.cost_analysis() or {}
        out["cases"][name] = {
            "native_s8_contraction": bool(
                has_s32_contraction and not widening_convert),
            "estimated_cycles": max(cycles) if cycles else None,
            "int8_packed_layout_T8_128_4_1": "(4,1)" in hlo,
            "contraction_hlo": _dot_lines(hlo)[:12],
            "flops": ca.get("flops"),
        }

    M = 512
    probe("int8_matmul_s32acc",
          lambda a, b: lax.dot_general(
              a, b, (((1,), (0,)), ((), ())),
              preferred_element_type=jnp.int32),
          jax.ShapeDtypeStruct((M, M), jnp.int8),
          jax.ShapeDtypeStruct((M, M), jnp.int8))
    probe("bf16_matmul_f32acc_control",
          lambda a, b: lax.dot_general(
              a, b, (((1,), (0,)), ((), ())),
              preferred_element_type=jnp.float32),
          jax.ShapeDtypeStruct((M, M), jnp.bfloat16),
          jax.ShapeDtypeStruct((M, M), jnp.bfloat16))
    probe("int8_conv_s32acc",
          lambda x, k: lax.conv_general_dilated(
              x, k, (1, 1), "SAME",
              dimension_numbers=("NHWC", "HWIO", "NHWC"),
              preferred_element_type=jnp.int32),
          jax.ShapeDtypeStruct((1, 28, 28, 64), jnp.int8),
          jax.ShapeDtypeStruct((3, 3, 64, 64), jnp.int8))

    i8 = out["cases"]["int8_matmul_s32acc"]["estimated_cycles"]
    bf = out["cases"]["bf16_matmul_f32acc_control"]["estimated_cycles"]
    if i8 and bf:
        out["int8_vs_bf16_matmul_cycle_ratio"] = round(i8 / bf, 3)

    blob = json.dumps(out, indent=1)
    print(blob)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(blob + "\n")


if __name__ == "__main__":
    main()

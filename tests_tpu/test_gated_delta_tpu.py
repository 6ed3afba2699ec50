"""Qwen3-Next's kernels on the chip at the published head sizes
(``qwen3_next.chat_decode_sat``): the gated delta rule's two served forms
(``mxnet_tpu/ops/gated_delta.py``) against the module's plain recurrence, what
a compiled step program holds of the state, and the paged and prefill attention
kernels at heads of 256 with 8 query heads a KV head.

Shapes: 256 slots of 32 heads of (128, 128) float32 (2 MiB a slot a layer,
512 MiB a pool); a 512-row prompt, whole and 397 rows true in the 512 bucket;
16 query / 2 KV heads of 256, blocks of 16, bf16.

Tolerances.  The rule is float32 on both sides and the kernel's sums run in
another order than the recurrence's (sublane trees against XLA's reduce), the
chunked scan's through a triangular solve: ``1e-4`` of the largest value after
512 tokens.  Attention: as ``test_paged_attention_tpu`` (``4 * EPS``).

``chiprun_out/gated_delta_tpu.json`` keeps the forms' times: the kernel's and
the XLA form's seconds a call on the whole pool (donated, as a step program
holds it), and the chunked scan's.
"""
import json
import os
import time

import numpy as np
import pytest

EPS = 2.0 ** -8
SLOTS, HEADS, DK, DV = 256, 32, 128, 128
FACTS = {}


def _inputs(key, lead):
    """Rule inputs with ``lead`` leading axes before the heads: unit keys,
    queries at ``dk^-1/2``, decays of 0.9 to 0.999 a token."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], lead + (HEADS, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], lead + (HEADS, DK)))
    v = jax.random.normal(ks[2], lead + (HEADS, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], lead + (HEADS,)))
    g = -jnp.exp(jax.random.uniform(ks[4], lead + (HEADS,),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    return q, k, v, beta, g


def _seconds(fn, *args, reps=10):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times))


def test_step_kernel_matches_the_recurrence_and_reads_the_pool_once(
        parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta as gd

    assert gd.step_applicable("tpu", None, HEADS, DK, DV)
    assert gd.step_form((HEADS, DK, DV)) == "step_kernel"
    pool = jax.random.normal(jax.random.PRNGKey(1), (SLOTS, HEADS, DK, DV))
    x = _inputs(jax.random.PRNGKey(2), (SLOTS,))
    live = jnp.arange(SLOTS) % 5 != 0
    want_o, want_s = jax.jit(gd._one_token)(
        pool, x[0], x[1], x[2], jnp.where(live[:, None], x[3], 0.0),
        jnp.where(live[:, None], x[4], 0.0))
    forms = {name: jax.jit(lambda pool, x, live, kernel=kernel: gd.step(
        pool, *x, live=live, kernel=kernel))
        for name, kernel in (("kernel", True), ("xla", False))}
    for name, fn in forms.items():
        o, s = fn(pool, x, live)
        for got, want, what in ((o, want_o, "o"), (s, want_s, "state")):
            got, want = np.asarray(got), np.asarray(want)
            err = float(np.abs(got - want).max() / np.abs(want).max())
            parity_record("gated_delta", f"step_{name}_{what}", err)
            assert err < 1e-4, (name, what, err)
        # a slot this step does not own keeps its state to the bit
        assert np.array_equal(np.asarray(s)[::5], np.asarray(pool)[::5])
    # donated, the kernel's program holds no second pool
    donated = jax.jit(lambda pool, x: gd.step(pool, *x, kernel=True),
                      donate_argnums=0)
    comp = donated.lower(pool, x).compile()
    text = comp.as_text()
    assert "gated_delta_step" in text
    assert comp.memory_analysis().temp_size_in_bytes < pool.nbytes // 8
    # timed as the step program holds the pool: donated and handed on (without
    # donation XLA copies the pool in front of the aliased kernel, which
    # triples its traffic: 4.4 ms a call against 1.7)
    nbytes = 2 * pool.nbytes
    for name, kernel in (("kernel", True), ("xla", False)):
        fn = jax.jit(lambda pool, x, live, kernel=kernel: gd.step(
            pool, *x, live=live, kernel=kernel), donate_argnums=0)
        held = pool + 0.0
        times = []
        for _ in range(12):
            t = time.perf_counter()
            _o, held = fn(held, x, live)
            jax.block_until_ready(held)
            times.append(time.perf_counter() - t)
        sec = float(np.median(times[2:]))
        FACTS[f"step_{name}_s"] = sec
        FACTS[f"step_{name}_gb_per_s"] = nbytes / sec / 1e9
    print("gated_delta step:", json.dumps(FACTS))


@pytest.mark.parametrize("true_rows", [512, 397])
def test_chunk_scan_matches_the_recurrence_over_a_512_row_prompt(
        true_rows, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta as gd

    x = _inputs(jax.random.PRNGKey(3), (1, 512))
    live = jnp.arange(512)[None] < true_rows
    o, s = jax.jit(lambda x, live: gd.chunk_scan(*x, live=live))(x, live)
    want_o, want_s = jax.jit(lambda x: gd.recurrence(*x))(
        tuple(a[:, :true_rows] for a in x))
    for got, want, what in ((o[:, :true_rows], want_o, "o"),
                            (s, want_s, "state")):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all()
        err = float(np.abs(got - want).max() / np.abs(want).max())
        parity_record("gated_delta", f"scan_{true_rows}_{what}", err)
        assert err < 1e-4, (what, err)
    if true_rows == 512:
        scan = jax.jit(lambda x: gd.chunk_scan(*x))
        FACTS["scan_512_rows_s"] = _seconds(scan, x)
        FACTS["recurrence_512_rows_s"] = _seconds(
            jax.jit(lambda x: gd.recurrence(*x)), x, reps=3)
        print("gated_delta scan:", json.dumps(FACTS))


def _one_period():
    """Published operator widths, one period (3 delta-rule layers and one
    attention layer), 8 of 32 experts held, a vocabulary of 1,024."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.qwen3_next import (Qwen3NextConfig,
                                             Qwen3NextForCausalLM)

    mx.random.seed(3)
    net = Qwen3NextForCausalLM(Qwen3NextConfig(
        num_layers=4, num_experts=32, experts_held=(0, 8), vocab_size=1024,
        max_seq_len=512))
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    return net


def test_compiled_step_holds_no_copy_of_a_state_array():
    """The engine picks the kernel, keeps the recurrent state float32 beside a
    bf16 ring, and its compiled step program (pools donated) has no copy or
    convert of either array's size."""
    import re

    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerConfig

    srv = serving.GenerativeServer(_one_period(), ServerConfig(
        max_batch=1, max_length=512, min_length=32, num_slots=SLOTS,
        block_size=16))
    eng = srv.engine
    assert eng.linear_attention == "step_kernel"
    ring, state = eng._pool[0]
    assert (state.shape, str(state.dtype)) == ((SLOTS, HEADS, DK, DV), "float32")
    assert (ring.shape, str(ring.dtype)) == ((SLOTS, 3, 8192), "bfloat16")
    comp = eng._step.lower(eng._w, eng._pool, eng._dev(eng._tables),
                           eng._dev(eng._last), eng._toks,
                           eng._dev(eng._pos)).compile()
    text = comp.as_text()
    assert text.count("gated_delta_step") >= 3
    shapes = (f"f32[{SLOTS},{HEADS},{DK},{DV}]", f"bf16[{SLOTS},3,8192]")
    bad = [line.strip()[:200] for line in text.splitlines()
           if re.search(r"= \S+ (copy|convert|copy-start)\(", line)
           and any(s in line.split("=", 1)[1].split("(", 1)[0] for s in shapes)]
    assert not bad, bad
    # nothing pool-sized beside the donated pools
    assert comp.memory_analysis().temp_size_in_bytes < state.nbytes
    FACTS["step_program_temp_bytes"] = int(
        comp.memory_analysis().temp_size_in_bytes)


def test_attention_kernels_at_heads_of_256(parity_record):
    """16 query / 2 KV heads of 256: the paged decode kernel over a ragged
    table and the prefill flash kernel, against ``masked_attention``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops.attention import masked_attention

    h, hkv, hd, bs = 16, 2, 256, 16
    slots, max_len, num_blocks = 64, 1536, 64 * 96
    assert pa.applicable("tpu", None, hd, hkv, bs, jnp.bfloat16) == 1
    rng = np.random.default_rng(5)
    lengths = rng.integers(1, max_len + 1, size=slots).astype(np.int32)
    lengths[:4] = [max_len, 1, bs, bs + 1]
    mb = max_len // bs
    order = rng.permutation(num_blocks)
    tables = np.full((slots, mb), num_blocks, np.int32)
    at = 0
    for s, n in enumerate(lengths):
        nblk = -(-int(n) // bs)
        tables[s, :nblk] = order[at:at + nblk]
        at += nblk
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    kp = jax.random.normal(keys[0], (num_blocks, hkv, bs, hd), jnp.bfloat16)
    vp = jax.random.normal(keys[1], (num_blocks, hkv, bs, hd), jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, h, hd), jnp.bfloat16)
    got = np.asarray(jax.jit(pa.paged_decode_attention)(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)), np.float32)

    def dense(q, kp, vp, tables, lengths):
        gat = jnp.minimum(tables, num_blocks - 1)
        kc = kp[gat].transpose(0, 2, 1, 3, 4).reshape(slots, hkv, -1, hd)
        vc = vp[gat].transpose(0, 2, 1, 3, 4).reshape(slots, hkv, -1, hd)
        mask = (jnp.arange(max_len)[None, :] < lengths[:, None])[:, None, None]
        return masked_attention(q[:, :, None], kc, vc, mask)[:, :, 0]

    want = np.asarray(jax.jit(dense)(q, kp, vp, jnp.asarray(tables),
                                     jnp.asarray(lengths)), np.float32)
    assert np.isfinite(got).all()
    parity_record("paged_attention", "hd256_g8",
                  float(np.abs(got - want).max() / np.abs(want).max()))
    np.testing.assert_allclose(got, want, rtol=4 * EPS, atol=4 * EPS)

    lp = 512
    assert fa.prefill_applicable("tpu", None, hd, lp)
    q = jax.random.normal(keys[0], (2, h, lp, hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, hkv, lp, hd), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, hkv, lp, hd), jnp.bfloat16)
    true = np.asarray([lp, 323], np.int32)
    got = np.asarray(fa.prefill_flash_attention(q, k, v, jnp.asarray(true)),
                     np.float32)
    want = np.asarray(jax.jit(masked_attention)(
        q, k, v, jnp.tril(jnp.ones((lp, lp), bool))), np.float32)
    for row, n in enumerate(true):
        g, w = got[row, :, :n], want[row, :, :n]
        parity_record("prefill_flash_attention", f"hd256_g8_row{row}",
                      float(np.abs(g - w).max() / np.abs(w).max()))
        np.testing.assert_allclose(g, w, rtol=4 * EPS, atol=4 * EPS)


def test_zz_the_forms_times_are_kept():
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "gated_delta_tpu.json"), "w") as f:
        json.dump(FACTS, f, indent=1, sort_keys=True)
    assert "step_kernel_s" in FACTS

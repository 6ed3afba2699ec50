"""The training flash kernels on the chip at ``bert_base.pretrain_s128``'s
shape, ``(128, 12, 128, 64)`` in bf16, where one tile holds a head's whole
sequence and a grid step takes several (batch, head) rows
(``mxnet_tpu/ops/flash_attention.py`` ``train_tiles``): the output and the
three gradients against ``_sdpa_ref`` in float32, what the three
``flash.rows_per_step.*`` gauges say, and that the step's working set
compiles inside the VMEM a kernel gets.

Tolerance: ``chip_smoke.py``'s kernel phase's — on the tensor, not the
element: relative rms error at most 2^-6 and no element further off than
2^-4 of the tensor's largest value (bf16 operands into the MXU, bf16
results, the backward's ``dp - delta`` cancellation)."""
import numpy as np
import pytest

SHAPE = (128, 12, 128, 64)
#: ``joyai_flash.pretrain_s4k``'s kernels by the rule (PR 45)
CELL_FORM = "pallas:fwd1024x1024,dq1024x1024,dkv512x512:d192/128:hb1"


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_kernels_match_reference_and_say_their_rows(causal, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import flash_attention as fa

    b, h, t, d = SHAPE
    hb = fa.train_tiles(b * h, t, t, d)
    assert hb > 1 and (b * h) % hb == 0
    scale = 1.0 / float(np.sqrt(d))
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v, g = (jax.random.normal(kk, SHAPE, jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)

    def kern(q, k, v, g):
        out, pull = jax.vjp(lambda a, b_, c: fa.flash_attention_raw(
            a, b_, c, causal, scale), q, k, v)
        return (out,) + pull(g)

    def ref(q, k, v, g):
        out, pull = jax.vjp(lambda a, b_, c: fa._sdpa_ref(
            a, b_, c, causal, scale),
            *(a.astype(jnp.float32) for a in (q, k, v)))
        return (out,) + pull(g.astype(jnp.float32))

    telemetry.enable()
    try:
        compiled = jax.jit(kern).lower(q, k, v, g).compile()
        gauges = telemetry.gauges()
    finally:
        telemetry.disable()
    # the choice is static: recorded where the program is traced
    assert [gauges[f"flash.rows_per_step.{n}"]
            for n in ("fwd", "dq", "dkv")] == [hb] * 3
    # three Mosaic calls; a step whose working set passed the VMEM a
    # kernel gets would not have compiled
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3
    assert hb * fa.train_row_bytes(t, t, d) <= fa.TRAIN_VMEM_BYTES
    got = jax.block_until_ready(compiled(q, k, v, g))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref)(q, k, v, g))
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - w) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        rel_max = float(np.abs(a - w).max() / np.abs(w).max())
        parity_record("train_flash_attention",
                      f"{name}_{'causal' if causal else 'full'}", rel_max)
        assert rel_rms <= 2.0 ** -6 and rel_max <= 2.0 ** -4, \
            (name, rel_rms, rel_max)


def test_rows_a_step_change_no_result_on_the_chip(monkeypatch):
    """One row a step (the program before the rule) and the rule's
    choice: the same arithmetic a row, so the same bf16 results."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    b, h, t, d = 16, 12, 128, 64
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
                  for kk in keys)

    def run():
        def kern(q, k, v, g):
            o, lse = fa._fa_forward_pallas(q, k, v, False, 0.125,
                                           with_lse=True)
            return (o, lse) + fa._fa_backward_pallas(
                q, k, v, o, g, lse, False, 0.125)
        return [np.asarray(x, np.float32)
                for x in jax.jit(lambda *a: kern(*a))(q, k, v, g)]

    chosen = run()
    monkeypatch.setattr(fa, "train_tiles", lambda *a: 1)
    for name, a, w in zip(("o", "lse", "dq", "dk", "dv"), chosen, run()):
        assert np.array_equal(a, w), name


def test_latent_heads_of_192_and_128_causal_at_4096():
    """``joyai_flash.pretrain_s4k``'s attention: q and k 192 wide (128 + 64
    rotary), v 128, causal, each kernel at ``train_blocks``' tiles, one row a
    step; a quarter of the cell's heads (the reference's float32 scores are
    2 GB at 8).  The output and the three gradients against ``_sdpa_ref`` in
    float32."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    b, h, t, d, dv = 1, 8, 4096, 192, 128
    assert fa.train_form((b, h, t, d), dv, causal=True) == CELL_FORM
    scale = d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, t, w), jnp.float32)
                  .astype(jnp.bfloat16)
                  for kk, w in zip(keys, (d, d, dv, dv)))

    def kern(q, k, v, g):
        out, pull = jax.vjp(lambda a, b_, c: fa.flash_attention_raw(
            a, b_, c, True, scale), q, k, v)
        return (out,) + pull(g)

    def ref(q, k, v, g):
        out, pull = jax.vjp(lambda a, b_, c: fa._sdpa_ref(
            a, b_, c, True, scale),
            *(a.astype(jnp.float32) for a in (q, k, v)))
        return (out,) + pull(g.astype(jnp.float32))

    compiled = jax.jit(kern).lower(q, k, v, g).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3
    got = jax.block_until_ready(compiled(q, k, v, g))
    assert [a.shape[-1] for a in got] == [dv, d, d, dv]
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref)(q, k, v, g))
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - w) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        rel_max = float(np.abs(a - w).max() / np.abs(w).max())
        assert rel_rms <= 2.0 ** -6 and rel_max <= 2.0 ** -4, \
            (name, rel_rms, rel_max)


def test_the_cells_kernels_at_the_rules_tiles_match_the_chunked_fall_back():
    """``(4, 32, 4096, 192 / 128)`` causal, the cell's whole attention call:
    forward, ``dq`` and ``dkv`` at ``train_blocks``' tiles against the
    chunked ``jax.numpy`` fall-back (float32 inside) that a CPU runs; three
    Mosaic calls that compile inside the VMEM a kernel gets by default (no
    ``vmem_limit_bytes`` is set: one that asked for more would be refused
    here), and the gauges of each grid."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import flash_attention as fa

    b, h, t, d, dv = 4, 32, 4096, 192, 128
    assert fa.train_form((b, h, t, d), dv, causal=True) == CELL_FORM
    scale = d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q, k, v, g = (jax.random.normal(kk, (b, h, t, w), jnp.float32)
                  .astype(jnp.bfloat16)
                  for kk, w in zip(keys, (d, d, dv, dv)))

    def kern(q, k, v, g):
        o, lse = fa._fa_forward_pallas(q, k, v, True, scale, with_lse=True)
        return (o,) + fa._fa_backward_pallas(q, k, v, o, g, lse, True, scale)

    def ref(q, k, v, g):
        o = fa._fa_forward_chunked(q, k, v, True, scale)
        return (o,) + fa._fa_backward(q, k, v, o, g, True, scale)

    telemetry.enable()
    try:
        compiled = jax.jit(kern).lower(q, k, v, g).compile()
        gauges = telemetry.gauges()
    finally:
        telemetry.disable()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "vmem_limit_bytes" not in text
    # a head's grid is the steps under the diagonal: 10 of 4 x 4 tiles of
    # 1,024, 36 of 8 x 8 of 512
    assert [(gauges[f"flash.grid_steps.{n}"], gauges[f"flash.live_steps.{n}"])
            for n in ("fwd", "dq", "dkv")] == [(10, 10), (10, 10), (36, 36)]
    got = jax.block_until_ready(compiled(q, k, v, g))
    want = jax.block_until_ready(jax.jit(ref)(q, k, v, g))
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - w) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        rel_max = float(np.abs(a - w).max() / np.abs(w).max())
        assert rel_rms <= 2.0 ** -6 and rel_max <= 2.0 ** -4, \
            (name, rel_rms, rel_max)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_token_major_kernels_match_the_dense_vjp(causal, parity_record):
    """``bert_base.pretrain_s128``'s attention as the model calls it:
    ``sdpa_raw`` on ``(128, 128, 12, 64)`` bf16, a reshape of the
    projections' ``(B, T, N x H)``.  The rule takes the token-major entry
    (the gauges say so, 16 (batch, head) rows a step), the program holds
    three Mosaic calls and no transpose or copy of an operand, and the
    output and the three gradients match ``_sdpa_ref``'s in float32; they
    also match the head-major kernels' on the same values to the last
    bit but ``delta``'s, which the token-major kernels sum themselves."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import sdpa_raw

    b, n, t, d = SHAPE
    scale = 1.0 / float(np.sqrt(d))
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q, k, v, g = (jax.random.normal(kk, (b, t, n * d), jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)

    def heads(x):
        return x.reshape(b, t, n, d)

    def kern(q, k, v, g):
        out, pull = jax.vjp(lambda a, b_, c: sdpa_raw(
            heads(a), heads(b_), heads(c), scale=scale, causal=causal)
            .reshape(b, t, n * d), q, k, v)
        return (out,) + pull(g)

    def head_major(q, k, v, g):
        def tr(x):
            return heads(x).transpose(0, 2, 1, 3)
        out, pull = jax.vjp(lambda a, b_, c: fa.flash_attention_raw(
            tr(a), tr(b_), tr(c), causal, scale).transpose(0, 2, 1, 3)
            .reshape(b, t, n * d), q, k, v)
        return (out,) + pull(g)

    def ref(q, k, v, g):
        def tr(x):
            return heads(x.astype(jnp.float32)).transpose(0, 2, 1, 3)
        out, pull = jax.vjp(lambda a, b_, c: fa._sdpa_ref(
            tr(a), tr(b_), tr(c), causal, scale).transpose(0, 2, 1, 3)
            .reshape(b, t, n * d), q, k, v)
        return (out,) + pull(g.astype(jnp.float32))

    assert fa.train_form((b, t, n, d), causal=causal, layout="tokens") \
        == "pallas:fwd128x128,dq128x128,dkv128x128:d64/64:hb16:tokens"
    telemetry.enable()
    try:
        compiled = jax.jit(kern).lower(q, k, v, g).compile()
        gauges = telemetry.gauges()
    finally:
        telemetry.disable()
    for name in ("fwd", "dq", "dkv"):
        assert gauges[f"flash.token_major.{name}"] == 1
        assert gauges[f"flash.rows_per_step.{name}"] == 16
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " copy(" not in text and " transpose(" not in text
    got = jax.block_until_ready(compiled(q, k, v, g))
    other = jax.block_until_ready(jax.jit(head_major)(q, k, v, g))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref)(q, k, v, g))
    for name, a, o, w in zip(("out", "dq", "dk", "dv"), got, other, want):
        a, o, w = (np.asarray(x, np.float32) for x in (a, o, w))
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - w) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        rel_max = float(np.abs(a - w).max() / np.abs(w).max())
        parity_record("train_flash_attention_tokens",
                      f"{name}_{'causal' if causal else 'full'}", rel_max)
        assert rel_rms <= 2.0 ** -6 and rel_max <= 2.0 ** -4, \
            (name, rel_rms, rel_max)
        # the same arithmetic a head: the forward to the bit, the
        # gradients to a bf16 rounding of a sum taken in another order
        if name == "out":
            assert np.array_equal(a, o), name
        else:
            assert np.abs(a - o).max() <= 2.0 ** -7 * np.abs(o).max(), name

"""A block decoder's two attention paths on the chip, at
``sdar_30b.chat_decode_sat``'s shapes (32 query / 4 KV heads of 128, bf16):
the block window through ``ops.paged_attention.paged_decode_attention`` (4
columns a slot that all see the block's end; 128 slots x 1024 tokens, a pool
of 8192 blocks of 16) against the gather path's arithmetic, and the
block-masked ``ops.flash_attention.prefill_flash_attention`` (``span`` 4) at
the buckets 256 and 512 against ``masked_attention`` under the block mask.

Tolerance: as ``test_paged_attention_tpu``'s and ``test_prefill_flash_tpu``'s:
``4 * EPS`` relative and absolute (probabilities and output rounded to bf16
on both sides).
"""
import numpy as np
import pytest

EPS = 2.0 ** -8
H, HKV, HD, BS, BL = 32, 4, 128, 16, 4
SLOTS, MAX_LEN, NUM_BLOCKS = 128, 1024, 8192


def test_block_window_kernel_matches_the_gather_path(parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged_attention as pa

    assert pa.applicable("tpu", None, HD, HKV, BS, jnp.bfloat16) == 1
    rng = np.random.default_rng(5)
    mb = MAX_LEN // BS
    # each slot's block starts at a multiple of 4: the first block, one
    # that straddles nothing (blocks of 16 hold 4 of them), the last one
    pos0 = rng.integers(0, MAX_LEN // BL, size=SLOTS) * BL
    pos0[:5] = [0, BL, BS - BL, BS, MAX_LEN - BL]
    order = rng.permutation(NUM_BLOCKS)
    tables = np.full((SLOTS, mb), NUM_BLOCKS, np.int32)
    at = 0
    for s, p in enumerate(pos0):
        n = -(-(int(p) + BL) // BS)
        tables[s, :n] = order[at:at + n]
        at += n
    tables[7] = NUM_BLOCKS                          # a vacant slot
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (SLOTS, BL, H, HD), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (NUM_BLOCKS, HKV, BS, HD), jnp.bfloat16)
    vp = jax.random.normal(keys[2], (NUM_BLOCKS, HKV, BS, HD), jnp.bfloat16)
    tab, first = jnp.asarray(tables), jnp.asarray(pos0, jnp.int32)
    got = np.asarray(pa.paged_decode_attention(
        q, kp, vp, tab, first + 1, block=True), np.float32)
    causal = np.asarray(pa.paged_decode_attention(
        q, kp, vp, tab, first + 1), np.float32)

    @jax.jit
    def gather(q, kp, vp, tab, first):
        pw = first[:, None] + jnp.arange(BL)[None]
        win = pa.window(kp, tab, pw, MAX_LEN, False, block=True)
        return pa.window_attention(q.transpose(0, 2, 1, 3), kp, vp, win)

    want = np.asarray(gather(q, kp, vp, tab, first), np.float32)
    live = np.ones(SLOTS, bool)
    live[7] = False
    assert np.isfinite(got).all() and not got[7].any()
    parity_record("paged_decode_attention", "block_window_128x1024",
                  float(np.abs(got[live] - want[live]).max()
                        / np.abs(want[live]).max()))
    np.testing.assert_allclose(got[live], want[live], rtol=4 * EPS,
                               atol=4 * EPS)
    # the last column sees the same rows under either bound; the first
    # does not see the other three under the causal one
    np.testing.assert_allclose(got[live, -1], causal[live, -1],
                               rtol=4 * EPS, atol=4 * EPS)
    assert np.abs(got[live, 0] - causal[live, 0]).max() > 0.05


@pytest.mark.parametrize("lp", [256, 512])
def test_block_masked_prefill_kernel_matches_masked_attention(
        lp, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import masked_attention

    assert fa.prefill_applicable("tpu", None, HD, lp)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (2, H, lp, HD), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, HKV, lp, HD), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, HKV, lp, HD), jnp.bfloat16)
    lengths = np.asarray([lp, int(lp * 0.63) // BL * BL], np.int32)
    got = np.asarray(fa.prefill_flash_attention(
        q, k, v, jnp.asarray(lengths), span=BL), np.float32)
    p = jnp.arange(lp)
    mask = p[None, :] < (p[:, None] // BL + 1) * BL
    want = np.asarray(jax.jit(masked_attention)(q, k, v, mask), np.float32)
    causal = np.asarray(fa.prefill_flash_attention(
        q, k, v, jnp.asarray(lengths)), np.float32)
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        g, w = got[row, :, :n], want[row, :, :n]
        parity_record("prefill_flash_attention", f"span4_{lp}_row{row}",
                      float(np.abs(g - w).max() / np.abs(w).max()))
        np.testing.assert_allclose(g, w, rtol=4 * EPS, atol=4 * EPS)
        assert np.abs(causal[row, :, :n] - w).max() > 0.05

"""The looped decoder's shapes on the chip (``models/ouro.py``,
``models/decoder.py``'s loop over passes): the paged decode kernel and the
prefill's flash kernel at plain multi-head attention, 16 KV heads each serving
ONE query head of 128 (``group`` 1: the benchmark's other cells run 4 and 8),
against ``ops.attention.masked_attention``; and the step program of
``ouro_2_6b.reason_decode_sat`` cut to two layers (8 slots x 1,536 tokens, a
pool of 4 x 320 blocks of 16 a layer), the kernel against the gather path.

Tolerance: as ``test_paged_attention_tpu``'s: the kernels against the dense
attention to ``4 * EPS`` relative and absolute (probabilities and output
rounded to bf16 on both sides); the programs' logits in units of a position's
logit standard deviation, as the benchmark's check reads them.
"""
import re

import numpy as np
import pytest

EPS = 2.0 ** -8
SLOTS, MAX_LEN, NUM_BLOCKS, PASSES, BS, H, HD = 8, 1536, 320, 4, 16, 16, 128


def _tables(rng):
    """Ragged slots over a pool that holds about five of them: a full one, a
    one-token one, block edges and a vacant one among random lengths."""
    mb = MAX_LEN // BS
    lengths = np.asarray([MAX_LEN, 1, BS, BS + 1, 0, 700, 911, 333])
    order = rng.permutation(NUM_BLOCKS)
    tables = np.full((SLOTS, mb), NUM_BLOCKS, np.int32)
    at = 0
    for s, n in enumerate(lengths):
        nblk = -(-int(n) // BS)
        tables[s, :nblk] = order[at:at + nblk]
        at += nblk
    assert at <= NUM_BLOCKS
    return tables, np.maximum(lengths, 1).astype(np.int32), lengths > 0


@pytest.mark.parametrize("t", [0, 3], ids=["pass0", "pass3"])
def test_paged_kernel_at_group_one_matches_masked_attention(t, parity_record):
    """Pass ``t``'s rows of a pool of ``PASSES x NUM_BLOCKS`` blocks, through
    the slots' tables moved to that pass's blocks."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops.attention import masked_attention

    assert pa.applicable("tpu", None, HD, H, BS, jnp.bfloat16) == 1
    tables, lengths, live = _tables(np.random.default_rng(21))
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    pool = (PASSES * NUM_BLOCKS, H, BS, HD)
    kp = jax.random.normal(keys[0], pool, jnp.bfloat16)
    vp = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q = jax.random.normal(keys[2], (SLOTS, H, HD), jnp.bfloat16)
    moved = pa.pass_blocks(jnp.asarray(tables), t, NUM_BLOCKS, PASSES)
    got = np.asarray(jax.jit(pa.paged_decode_attention)(
        q, kp, vp, moved, jnp.asarray(lengths)), np.float32)

    def dense(q, kp, vp, moved, lengths):
        gat = jnp.minimum(moved, kp.shape[0] - 1)
        kc, vc = (pa.gathered_view(p, gat, 1) for p in (kp, vp))
        mask = (jnp.arange(MAX_LEN)[None, :] < lengths[:, None])[:, None, None]
        return masked_attention(q[:, :, None, :], kc, vc, mask)[:, :, 0]

    want = np.asarray(jax.jit(dense)(q, kp, vp, moved, jnp.asarray(lengths)),
                      np.float32)
    assert np.isfinite(got).all() and not got[~live].any()
    err = np.abs(got[live] - want[live])
    parity_record("paged_attention", f"group1_pass{t}",
                  float(err.max() / np.abs(want[live]).max()))
    np.testing.assert_allclose(got[live], want[live], rtol=4 * EPS,
                               atol=4 * EPS)


@pytest.mark.parametrize("lp", [256, 512])
def test_prefill_kernel_at_group_one_matches_masked_attention(lp,
                                                              parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import masked_attention

    assert fa.prefill_applicable("tpu", None, HD, lp)
    assert fa.prefill_tiles(1, lp) == (lp, lp)
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (2, H, lp, HD), jnp.bfloat16)
               for kk in keys)
    lengths = np.asarray([lp, int(lp * 0.63)], np.int32)
    got = np.asarray(fa.prefill_flash_attention(
        q, k, v, jnp.asarray(lengths)), np.float32)
    want = np.asarray(jax.jit(masked_attention)(
        q, k, v, jnp.tril(jnp.ones((lp, lp), bool))), np.float32)
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        g, w = got[row, :, :n], want[row, :, :n]
        parity_record("prefill_flash_attention", f"group1_{lp}_row{row}",
                      float(np.abs(g - w).max() / np.abs(w).max()))
        np.testing.assert_allclose(g, w, rtol=4 * EPS, atol=4 * EPS)


def test_looped_step_program():
    """The engine picks the kernel; the step program keeps its name and holds
    the stack ONCE (a kernel a layer, not a layer a pass) inside one loop, no
    array of a gathered view's size and no pool-sized copy; its logits follow
    the gather path's, every pass reading its own rows."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.models.ouro import OuroConfig, OuroForCausalLM
    from mxnet_tpu.serving.generative import LlamaServingEngine

    mx.random.seed(3)
    net = OuroForCausalLM(OuroConfig(num_layers=2, max_seq_len=MAX_LEN))
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    eng = LlamaServingEngine(net, max_len=MAX_LEN, num_slots=SLOTS,
                             block_size=BS, num_blocks=NUM_BLOCKS)
    stored = (PASSES * NUM_BLOCKS, H, BS, HD)
    assert eng.decode_attention == "paged_kernel" and eng.kv_pack == 1
    assert eng._pool[0][0].shape == stored
    assert eng.kv_bytes_per_token == 2 * PASSES * 2 * H * HD * 2
    rng = np.random.default_rng(22)
    tables, lengths, live = _tables(rng)
    eng._tables[:] = tables
    eng._pos[:] = lengths - 1
    eng._last[:] = rng.integers(1, 49152, size=SLOTS)
    keys = jax.random.split(jax.random.PRNGKey(6), 2 * len(eng._pool))
    eng._pool = [tuple(jax.random.normal(k, kp.shape, kp.dtype)
                       for k, kp in zip(keys[2 * l:2 * l + 2], pair))
                 for l, pair in enumerate(eng._pool)]
    args = (eng._w, eng._pool, jnp.asarray(eng._tables),
            jnp.asarray(eng._last), jnp.asarray(eng._pos))
    text = eng._step.lower(*args[:4], eng._toks, args[4]).compile().as_text()
    assert re.search(r"^HloModule jit__step_fn\b", text, re.M)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert text.count(" while(") == 1
    mb = MAX_LEN // BS
    assert f"[{SLOTS},{H},{MAX_LEN},{HD}]" not in text
    assert f"[{SLOTS},{mb},{H},{BS},{HD}]" not in text
    pool_copy = re.compile(
        r"= \w+\[" + ",".join(map(str, stored)) +
        r"\]\S* (copy|convert|transpose)\(")
    assert not pool_copy.search(text), pool_copy.search(text).group(0)

    dec = eng._dec
    logits = {path: np.asarray(jax.jit(
        lambda w, pools, *a, path=path: dec._step_blocks_impl(
            w, pools, *a, paged_kernel=path)[0])(*args), np.float32)
        for path in (True, False)}
    got, want = logits[True][live], logits[False][live]
    assert np.isfinite(got).all()
    # eight layer applications where the two-layer step programs of
    # ``test_paged_attention_tpu`` have two and hold 0.1: the two paths'
    # roundings add as a walk, twice as far (it read 0.103, PR 40)
    unit = want.std(axis=-1, keepdims=True)
    assert (np.abs(got - want) / unit).max() < 0.25
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.8
    for _ in range(3):
        eng.step([s for s in range(SLOTS) if live[s]
                  and eng._pos[s] + 1 < MAX_LEN])
    assert eng.compiled_signatures() == [("step",)]

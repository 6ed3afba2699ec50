"""Paged decode attention on the chip (``mxnet_tpu/ops/paged_attention.py``):
the Mosaic kernel against the gather path at the serving cells' shapes, and
what the compiled step program holds.

Shapes: ``mistral7b.chat_decode_sat`` (64 slots x 1024 tokens, pool of 4096
blocks) and ``mistral7b.doc_prefill`` (16 x 4096, 2048 blocks), 32 query / 8
KV heads of 128; ``lfm2_24b.chat_decode_sat`` (128 x 1024, 8192 blocks), 32 /
8 heads of 64, the pool stored two KV heads to a 128-lane row, ``(8192, 4,
16, 128)``; blocks of 16, bf16.  The step programs are a dense decoder's cut
to two layers (at heads of 64, Llama-3.2-1B's widths): every layer's arrays
have the cells' shapes.

Tolerance: one contraction over softmax weights that sum to 1 and values of
unit scale, probabilities and output rounded to bf16 on both sides
(``EPS = 2**-8``): ``rtol 4 * EPS`` as the MXU tier of ``test_tpu_parity``
and an absolute ``4 * EPS`` for the cancelling elements.  The flash
kernel's backward tier (``rtol 2**-4, atol 0.1``) would pass a result that
is wrong in its first digit here, where outputs are 0.05-0.5.
"""
import re

import numpy as np
import pytest

EPS = 2.0 ** -8
#: cell -> (slots, max_len, num_blocks, head_dim)
CELLS = {"chat_64x1024": (64, 1024, 4096, 128),
         "doc_16x4096": (16, 4096, 2048, 128),
         "lfm2_128x1024_hd64": (128, 1024, 8192, 64)}
BS, H, HKV = 16, 32, 8


def _case(rng, slots, max_len, num_blocks):
    """A ragged table: full, one-token, block-edge and vacant slots among
    random lengths, blocks dealt from a shuffled pool."""
    mb = max_len // BS
    lengths = rng.integers(1, max_len + 1, size=slots)
    lengths[:6] = [max_len, 1, BS, BS + 1, BS - 1, 0]
    budget = num_blocks * BS
    while lengths.sum() > budget:
        lengths[int(np.argmax(lengths[1:])) + 1] //= 2
    order = rng.permutation(num_blocks)
    tables = np.full((slots, mb), num_blocks, np.int32)
    at = 0
    for s, n in enumerate(lengths):
        nblk = -(-int(n) // BS)
        tables[s, :nblk] = order[at:at + nblk]
        at += nblk
    live = lengths > 0
    return tables, np.maximum(lengths, 1).astype(np.int32), live


def _gathered(q, kp, vp, tables, lengths):
    """The gather path's arithmetic on UNPACKED pools (NB, HKV, BS, hd)."""
    import jax
    import jax.numpy as jnp

    s, hd = q.shape[0], q.shape[-1]
    gat = jnp.minimum(tables, kp.shape[0] - 1)
    kc = kp[gat].transpose(0, 2, 1, 3, 4).reshape(s, HKV, -1, hd)
    vc = vp[gat].transpose(0, 2, 1, 3, 4).reshape(s, HKV, -1, hd)
    kc, vc = (jnp.repeat(a, H // HKV, axis=1) for a in (kc, vc))
    sc = jnp.einsum("shd,shtd->sht", q, kc,
                    preferred_element_type=jnp.float32) / np.sqrt(hd)
    mask = jnp.arange(kc.shape[2])[None, :] < lengths[:, None]
    sc = jnp.where(mask[:, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,shtd->shd", p, vc)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_kernel_matches_gather_path(cell, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged_attention as pa

    slots, max_len, num_blocks, hd = CELLS[cell]
    rng = np.random.default_rng(11)
    tables, lengths, live = _case(rng, slots, max_len, num_blocks)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    pool = (num_blocks, HKV, BS, hd)
    kp = jax.random.normal(keys[0], pool, jnp.bfloat16)
    vp = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, H, hd), jnp.bfloat16)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    pack = pa.applicable("tpu", None, hd, HKV, BS, kp.dtype)
    assert pack == 128 // hd
    got = np.asarray(jax.jit(pa.paged_decode_attention)(
        q, pa.pack_rows(kp, pack), pa.pack_rows(vp, pack), tables, lengths),
        np.float32)
    want = np.asarray(jax.jit(_gathered)(q, kp, vp, tables, lengths),
                      np.float32)
    assert np.isfinite(got).all()
    assert not got[~live].any()          # a vacant slot reads nothing
    err = np.abs(got[live] - want[live])
    parity_record("paged_attention", cell,
                  float(err.max() / max(np.abs(want[live]).max(), 1e-6)))
    np.testing.assert_allclose(got[live], want[live], rtol=4 * EPS,
                               atol=4 * EPS)


@pytest.fixture(scope="module")
def nets():
    """head_dim -> a two-layer bf16 decoder, seeded, built once: Mistral-7B
    widths at 128, Llama-3.2-1B's at 64."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    built = {}

    def net(hd):
        if hd not in built:
            mx.random.seed(3)
            hidden, inter = {128: (4096, 14336), 64: (2048, 8192)}[hd]
            net = LlamaForCausalLM(LlamaConfig(
                hidden_size=hidden, intermediate_size=inter, num_layers=2,
                num_heads=H, num_kv_heads=HKV, vocab_size=32768,
                max_seq_len=4096, rope_theta=1e6, tie_embeddings=False))
            assert net.config.head_dim == hd
            net.cast("bfloat16")
            net.collect_params().setattr("grad_req", "null")
            net.initialize(mx.init.Normal(0.02))
            built[hd] = net
        return built[hd]

    return net


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_step_program(cell, nets):
    """The engine picks the kernel here and stores the pool in whole lane
    rows; its step program keeps its name and its one signature, holds no
    array of a gathered view's size and no pool-sized copy or convert;
    its logits follow the gather path's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving.generative import LlamaServingEngine

    slots, max_len, num_blocks, hd = CELLS[cell]
    mb, pack = max_len // BS, 128 // hd
    eng = LlamaServingEngine(nets(hd), max_len=max_len, num_slots=slots,
                             block_size=BS, num_blocks=num_blocks)
    stored = (num_blocks, HKV // pack, BS, 128)
    assert eng.kv_pack == pack and eng._pool[0][0].shape == stored
    assert eng.decode_attention == "paged_kernel"
    rng = np.random.default_rng(12)
    tables, lengths, live = _case(rng, slots, max_len, num_blocks)
    # the step writes row pos = lengths - 1 and attends lengths rows
    eng._tables[:] = tables
    eng._pos[:] = lengths - 1
    eng._last[:] = rng.integers(1, 32768, size=slots)
    keys = jax.random.split(jax.random.PRNGKey(6), 2 * len(eng._pool))
    eng._pool = [tuple(jax.random.normal(k, kp.shape, kp.dtype)
                       for k, kp in zip(keys[2 * l:2 * l + 2], pair))
                 for l, pair in enumerate(eng._pool)]
    args = (eng._w, eng._pool, jnp.asarray(eng._tables),
            jnp.asarray(eng._last), jnp.asarray(eng._pos))

    # the engine's step also takes the step before's output (``prev``)
    text = eng._step.lower(*args[:4], eng._toks,
                           args[4]).compile().as_text()
    assert re.search(r"^HloModule jit__step_fn\b", text, re.M)
    # one kernel a layer
    assert text.count('custom_call_target="tpu_custom_call"') == \
        len(eng._pool)
    for kv in (HKV // pack, HKV, H):
        for lanes in (hd, 128):
            assert f"[{slots},{kv},{max_len},{lanes}]" not in text
            assert f"[{slots},{mb},{kv},{BS},{lanes}]" not in text
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
    # logits over two layers and the head: the standard deviation of a
    # position's logits is the unit, as the benchmark's check reads them
    unit = want.std(axis=-1, keepdims=True)
    assert (np.abs(got - want) / unit).max() < 0.1
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.95

    for _ in range(3):
        eng.step([s for s in range(slots) if live[s]
                  and eng._pos[s] + 1 < max_len])
    assert eng.compiled_signatures() == [("step",)]


def test_the_draft_of_a_speculating_server_runs_the_kernels_too(nets):
    """The draft is a paged engine whose table never changes: on the chip
    its step takes the paged kernel and its long prefill the flash kernel,
    as the target's do, and a same-net draft is accepted most of the time
    (the draft's step and the target's verify are two programs in bf16: a
    near tie may flip, so not every proposal is)."""
    from mxnet_tpu import serving

    net = nets(128)
    cfg = serving.ServerConfig(
        max_batch=2, max_length=512, min_length=64, num_slots=4,
        block_size=BS, summary_every=1 << 30, draft_net=net, spec_k=3)
    srv = serving.GenerativeServer(net, cfg)
    rep = srv.replicas[0]
    draft = rep.draft
    assert draft.decode_attention == rep.engine.decode_attention \
        == "paged_kernel"
    assert draft.prefill_attention == rep.engine.prefill_attention == "flash"
    assert draft.num_blocks == 4 * (512 // BS)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 32768, size=n) for n in (40, 300, 129, 64)]
    with srv:
        outs = [f.result(300) for f in
                [srv.submit(p, max_new_tokens=24) for p in prompts]]
        stats = srv.stats()
    assert all(len(o) == len(p) + 24 for o, p in zip(outs, prompts))
    assert stats["failed"] == 0
    spec = stats["speculative"]
    assert spec["draft_tokens"] > 0 and spec["accept_rate"] >= 0.5, spec
    assert (draft._tables == draft.num_blocks).all()     # all released
    assert ("step",) in draft.compiled_signatures()

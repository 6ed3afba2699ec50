"""The served prefill's flash forward kernel on the chip
(``mxnet_tpu/ops/flash_attention.py`` ``prefill_flash_attention``): the
Mosaic kernel against ``ops.attention.masked_attention``, and the prefill
program at published widths through it against the dense path on the same
weights.

Shapes: ``mistral7b.doc_prefill``'s buckets 512 and 4,096 (32 query / 8 KV
heads of 128, hidden 4,096, feed-forward 14,336) and
``lfm2_24b.chat_decode_sat``'s bucket 512 (32 / 8 heads of 64 with q/k head
norms, hidden 2,048, 64 experts of 1,536 four a token, a conv layer before
two attention layers); two attention layers each, so that the second's K
and V carry the first's attention at every position.

Tolerance: as ``test_paged_attention_tpu``'s — the kernel against the dense
attention to ``4 * EPS`` relative and absolute (probabilities and output
rounded to bf16 on both sides), the programs' logits in units of a
position's logit standard deviation, as the benchmark's check reads them.
"""
import numpy as np
import pytest

EPS = 2.0 ** -8
H, HKV = 32, 8


@pytest.mark.parametrize("hd,lp", [(128, 512), (128, 4096), (64, 512)],
                         ids=["hd128_512", "hd128_4096", "hd64_512"])
def test_kernel_matches_masked_attention(hd, lp, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops.attention import masked_attention

    assert fa.prefill_applicable("tpu", None, hd, lp)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (2, H, lp, hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, HKV, lp, hd), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, HKV, lp, hd), jnp.bfloat16)
    lengths = np.asarray([lp, int(lp * 0.63)], np.int32)
    got = np.asarray(fa.prefill_flash_attention(
        q, k, v, jnp.asarray(lengths)), np.float32)
    want = np.asarray(jax.jit(masked_attention)(
        q, k, v, jnp.tril(jnp.ones((lp, lp), bool))), np.float32)
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        g, w = got[row, :, :n], want[row, :, :n]
        parity_record("prefill_flash_attention", f"hd{hd}_{lp}_row{row}",
                      float(np.abs(g - w).max() / np.abs(w).max()))
        np.testing.assert_allclose(g, w, rtol=4 * EPS, atol=4 * EPS)


def _mistral():
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
    return LlamaDecoder(net, max_len=4096)


def _lfm2():
    import mxnet_tpu as mx
    from mxnet_tpu.models import lfm2

    mx.random.seed(4)
    net = lfm2.Lfm2MoeForCausalLM(lfm2.Lfm2MoeConfig(
        num_layers=3, num_dense_layers=1,
        layer_types=["conv", "full_attention", "full_attention"],
        max_seq_len=512))
    assert net.config.head_dim == 64
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    return lfm2.Lfm2Decoder(net, 512)


@pytest.fixture(scope="module")
def decoders():
    built = {}

    def get(name):
        if name not in built:
            built[name] = {"mistral": _mistral, "lfm2": _lfm2}[name]()
        return built[name]

    return get


@pytest.mark.parametrize("model,lp,rows", [
    ("mistral", 512, 2), ("mistral", 4096, 1), ("lfm2", 512, 2)],
    ids=["mistral_512", "mistral_4096", "lfm2_512"])
def test_prefill_program(model, lp, rows, decoders):
    """The compiled program holds one kernel an attention layer and no
    score tensor; real rows' logits, and the rows the last layer keeps,
    follow the dense path's."""
    import jax
    import jax.numpy as jnp

    dec = decoders(model)
    w = dec._weights()
    rng = np.random.default_rng(lp)
    vocab = w["emb"].shape[0]
    ids = jnp.asarray(rng.integers(1, vocab, size=(rows, lp)), jnp.int32)
    t0 = jnp.asarray([int(lp * 0.63), lp][-rows:], jnp.int32)
    progs = {flash: jax.jit(lambda w, ids, t0, flash=flash:
                            dec._prefill_rows_impl(w, ids, t0, flash=flash))
             for flash in (True, False)}
    text = progs[True].lower(w, ids, t0).compile().as_text()
    kv_layers = dec.cache_spec().kv_layers
    assert text.count('custom_call_target="tpu_custom_call"') == kv_layers
    assert "prefill_flash_attention" in text
    assert f"[{H},{lp},{lp}]" not in text

    got, want = progs[True](w, ids, t0), progs[False](w, ids, t0)
    logits = [np.asarray(o[1], np.float32) for o in (got, want)]
    assert np.isfinite(logits[0]).all()
    unit = logits[1].std(axis=-1, keepdims=True)
    assert (np.abs(logits[0] - logits[1]) / unit).max() < 0.1
    assert (logits[0].argmax(-1) == logits[1].argmax(-1)).all()
    # the last K/V layer's rows, over the positions a request owns.  Behind
    # a routed expert layer a position whose expert choice sits on a margin
    # flips with the last bit of its attention (PERF.md section 4, the
    # lfm2 rows) and its K/V then differ in the first digit: there the
    # share of positions within the tolerance is held, not the worst one
    share = 0.9 if dec.cache_spec().expert_layers else 1.0
    kept = [next(r for r in reversed(o[0]) if isinstance(r, tuple))
            for o in (got, want)]
    for a, b in zip(*kept):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        for row, n in enumerate(np.asarray(t0)):
            err = np.abs(a[row, :, :n] - b[row, :, :n]).max(axis=(0, 2))
            within = (err < 0.1 * b[row, :, :n].std()).mean()
            assert within >= share, (within, err.max())
            # a position's worst element is one bf16 step of a value of
            # 2-4 standard deviations off: 2^-6
            assert np.median(err) < 0.05 * b[row, :, :n].std()

"""The served prefill's attention through the flash forward kernel
(``ops.flash_attention.prefill_flash_attention``, GQA inside the kernel,
the operands fed in their own dtype): the kernel under the Pallas
interpreter against ``ops.attention.masked_attention`` under ``tril``, the
prefill programs of the tiny Llama and LFM2 decoders through it against
the dense path, the rule that picks it, and what an engine and its lane
log say of it; and the trainer's three kernels by the rows a grid step
takes (``train_tiles``; ``tests/test_llama.py``, which holds the whole
custom-vjp to the oracle, is a ``slow`` module that tier 1 leaves out).
The compiles for a described v5e are in ``tests/test_paged_attention.py``,
beside the paged kernel's."""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.models import decoder as decoder_mod
from mxnet_tpu.models import lfm2
from mxnet_tpu.models.llama import LlamaDecoder, llama_tiny
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops.attention import masked_attention


def _qkv(rng, b, hkv, group, lp, hd, dtype):
    q = jnp.asarray(rng.normal(size=(b, hkv * group, lp, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, lp, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, lp, hd)), dtype)
    return q, k, v


def _f32(a):
    return np.asarray(a, np.float32)


# --- the kernel alone --------------------------------------------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("lp", [128, 256, 512])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("group", [1, 4])
def test_kernel_matches_masked_attention(group, hd, lp, dtype):
    """The forward with the group inside the kernel, at the served
    entry's own tiles, against the dense attention it replaces: rows
    below each true length equal, padded rows finite.  A row of 0 (a
    vacant batch row) reads nothing and yields zeros."""
    rng = np.random.default_rng(lp + hd + group)
    q, k, v = _qkv(rng, 3, 2, group, lp, hd, jnp.dtype(dtype))
    lengths = np.asarray([lp, lp - 37, 0], np.int32)
    want = _f32(masked_attention(q, k, v, jnp.tril(jnp.ones((lp, lp), bool))))
    got = _f32(fa._prefill_flash_attention(q, k, v, jnp.asarray(lengths),
                                           interpret=True))
    assert got.shape == q.shape and np.isfinite(got).all()
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :, :n], want[row, :, :n],
                                   atol=tol, rtol=tol)
    assert not got[2].any()


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
def test_tiles_past_a_rows_length_are_skipped_not_misread(bq, bk):
    """Tiles of other sizes than the entry's, a diagonal that crosses
    tiles of unequal sides, lengths on and off a tile's edge."""
    lp, hd = 512, 128
    rng = np.random.default_rng(bq + bk)
    q, k, v = _qkv(rng, 4, 1, 4, lp, hd, jnp.bfloat16)
    lengths = np.asarray([512, 256, 257, 1], np.int32)
    want = _f32(masked_attention(q, k, v, jnp.tril(jnp.ones((lp, lp), bool))))
    got = _f32(fa._fa_forward_pallas(
        q, k, v, True, 1.0 / float(np.sqrt(hd)), bq, bk,
        lengths=jnp.asarray(lengths), interpret=True))
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :, :n], want[row, :, :n],
                                   atol=2e-2, rtol=2e-2)
    # a q tile of padding alone ran no tile: zeros
    assert not got[3, :, bq:].any()


def test_training_forward_keeps_its_lse_for_the_backward():
    """``G = 1`` with the log-sum-exp, as the trainer's ``_fwd`` calls
    it: the lse is the dense one, in bf16 and non-causal too."""
    rng = np.random.default_rng(5)
    q, k, v = _qkv(rng, 2, 2, 1, 256, 64, jnp.bfloat16)
    scale = 0.125
    for causal in (False, True):
        out, lse = fa._fa_forward_pallas(q, k, v, causal, scale,
                                         block_q=128, block_k=128,
                                         with_lse=True, interpret=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -jnp.inf)
        np.testing.assert_allclose(_f32(lse),
                                   _f32(jax.nn.logsumexp(s, axis=-1)),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(_f32(out),
                                   _f32(fa._sdpa_ref(q, k, v, causal, scale)),
                                   atol=2e-2, rtol=2e-2)


# --- the trainer's kernels: the rows a grid step takes ------------------------

def _train_kernels(q, k, v, do, causal):
    """Output, lse and the three gradients straight from the kernels'
    entries, as numpy float32."""
    scale = 1 / float(np.sqrt(q.shape[-1]))
    o, lse = fa._fa_forward_pallas(q, k, v, causal, scale, with_lse=True,
                                   interpret=True)
    grads = fa._fa_backward_pallas(q, k, v, o, do, lse, causal, scale,
                                   interpret=True)
    return [np.asarray(x, np.float32) for x in (o, lse, *grads)]


@pytest.mark.parametrize("causal", [False, True])
def test_training_kernels_match_the_dense_vjp(causal):
    """Forward, dq and dkv at BERT-base's heads (rows of 128, heads of 64,
    bf16: one tile a head, so ``train_tiles`` rows a step) against the
    float32 attention of the same rounded operands and its vjp, within a
    few bf16 steps of each tensor's largest value."""
    rng = np.random.default_rng(3)
    q, k, v, do = (jnp.asarray(rng.normal(size=(2, 6, 128, 64)),
                               jnp.bfloat16) for _ in range(4))
    assert fa.train_tiles(12, 128, 128, 64) == 12
    o, lse, *grads = _train_kernels(q, k, v, do, causal)
    want, pull = jax.vjp(
        lambda a, b, c: fa._sdpa_ref(a, b, c, causal, 0.125),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, got, ref, tol in zip(
            ("o", "dq", "dk", "dv"), (o, *grads),
            (want, *pull(do.astype(jnp.float32))),
            (2 ** -7, 2 ** -6, 2 ** -6, 2 ** -6)):
        ref = _f32(ref)
        assert np.abs(got - ref).max() < tol * np.abs(ref).max(), name


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hb", [1, 2, 4, 12])
def test_flash_rows_a_step_change_no_bit(monkeypatch, hb, causal):
    """However many (batch, head) rows a grid step takes, the forward's
    output and log-sum-exp and all three gradients are one row a step's
    to the bit (the arithmetic of a row is the same; rows share
    nothing), and the three gauges say what was chosen."""
    rng = np.random.default_rng(11)
    operands = tuple(jnp.asarray(rng.normal(size=(4, 12, 128, 64)),
                                 jnp.bfloat16) for _ in range(4))
    monkeypatch.setattr(fa, "train_tiles", lambda *a: 1)
    want = _train_kernels(*operands, causal)
    monkeypatch.setattr(fa, "train_tiles", lambda *a: hb)
    telemetry.enable()
    try:
        got = _train_kernels(*operands, causal)
        gauges = telemetry.gauges()
    finally:
        telemetry.disable()
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert np.isfinite(a).all(), name
        assert np.array_equal(a, b), name
    assert [gauges[f"flash.rows_per_step.{k}"]
            for k in ("fwd", "dq", "dkv")] == [hb] * 3


@pytest.mark.parametrize("tq,tk", [(640, 640), (1024, 1024), (2048, 2048),
                                   (4096, 4096), (128, 1024), (1024, 128)])
def test_train_tiles_is_one_past_one_tile(tq, tk):
    """More than one tile along either side: the grid is today's, a row
    a step."""
    assert tq // fa._divisor_block(tq, min(512, tq)) > 1 or \
        tk // fa._divisor_block(tk, min(512, tk)) > 1
    for bh in (8, 48, 1536):
        for d in (64, 128):
            assert fa.train_tiles(bh, tq, tk, d) == 1


@pytest.mark.parametrize("bh,t,d", [(1536, 128, 64), (48, 128, 64),
                                    (4, 256, 32), (64, 512, 128),
                                    (7, 128, 64), (1, 128, 64)])
def test_train_tiles_divides_and_fits(bh, t, d):
    """One tile a head: the rows a step divide ``bh`` and their working
    set stays inside what a kernel gets."""
    hb = fa.train_tiles(bh, t, t, d)
    assert 1 <= hb <= bh and bh % hb == 0
    assert hb * fa.train_row_bytes(t, t, d) <= fa.TRAIN_VMEM_BYTES \
        or hb == 1
    if bh in (1536, 48) and t == 128:
        assert hb > 1


@pytest.mark.parametrize("lp", [256, 1024, 4096])
@pytest.mark.parametrize("group", [4, 8])
def test_served_prefill_keeps_its_grid(monkeypatch, group, lp):
    """The served prefill's calls are ``bounded`` (true lengths): the
    rule is not consulted and the traced program's text is the one-row
    form's, whatever the rule would say."""
    q = jax.ShapeDtypeStruct((1, 2 * group, lp, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, lp, 128), jnp.bfloat16)
    n = jax.ShapeDtypeStruct((1,), jnp.int32)

    def text():
        return str(jax.make_jaxpr(fa._prefill_flash_attention)(q, kv, kv, n))

    monkeypatch.setattr(fa, "train_tiles", lambda *a: 1)
    one_row = text()
    asked = []
    monkeypatch.setattr(fa, "train_tiles",
                        lambda *a: asked.append(a) or 8)
    assert text() == one_row and not asked
    bq, bk = fa.prefill_tiles(group, lp)
    assert f"GridMapping(grid=(2, {lp // bq}, {lp // bk})," in one_row


# --- the rule ----------------------------------------------------------------

@pytest.mark.parametrize("platform,mesh,hd,lp,want", [
    ("cpu", None, 128, 4096, False),
    ("tpu", "a mesh", 128, 4096, False),
    ("tpu", None, 128, 32, False),
    ("tpu", None, 128, 64, False),
    ("tpu", None, 128, 128, False),
    ("tpu", None, 128, 256, True),
    ("tpu", None, 128, 512, True),
    ("tpu", None, 128, 1024, True),
    ("tpu", None, 128, 2048, True),
    ("tpu", None, 128, 4096, True),
    ("tpu", None, 64, 512, True),
    ("tpu", None, 64, 64, False),
    ("tpu", None, 128, 200, False),     # an exact-length offline prompt
    ("tpu", None, 32, 512, False),
    ("tpu", None, 96, 512, False),
], ids=lambda v: str(v))
def test_rule(platform, mesh, hd, lp, want):
    assert fa.prefill_applicable(platform, mesh, hd, lp) is want
    if want:
        bq, bk = fa.prefill_tiles(4, lp)
        assert lp % bq == 0 and lp % bk == 0 and bq % 128 == 0


# --- the prefill programs through the kernel --------------------------------

@pytest.fixture
def interpreted(monkeypatch):
    """The views' kernel routed through the interpreter."""
    monkeypatch.setattr(
        decoder_mod, "prefill_flash_attention",
        functools.partial(fa._prefill_flash_attention, interpret=True))


def _llama(hd, max_len=256):
    heads, kv_heads = {128: (2, 1), 64: (4, 2)}[hd]
    net = llama_tiny(hidden_size=256, intermediate_size=256,
                     num_heads=heads, num_kv_heads=kv_heads, num_layers=2,
                     max_seq_len=max_len)
    assert net.config.head_dim == hd
    net.initialize()
    return LlamaDecoder(net, max_len=max_len)


def _lfm2_hd64():
    net = lfm2.lfm2_moe_tiny(hidden_size=256, max_seq_len=256)
    assert net.config.head_dim == 64
    net.initialize()
    return lfm2.Lfm2Decoder(net, 256)


@pytest.mark.parametrize("build", [
    functools.partial(_llama, 128), functools.partial(_llama, 64),
    _lfm2_hd64], ids=["llama_hd128", "llama_hd64", "lfm2_hd64"])
def test_prefill_program_through_kernel_matches_dense(build, interpreted):
    """``_prefill_rows_impl`` with ``flash`` against without: the logits
    at each row's last real position, the rows a K/V layer keeps (the
    first layer's bit for bit: no attention has touched its input), a
    state layer's state of the true length, and the expert rows."""
    dec = build()
    w = dec._weights()
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(1, 250, size=(2, 256)), jnp.int32)
    t0 = jnp.asarray([256, 150], jnp.int32)
    want = dec._prefill_rows_impl(w, ids, t0)
    got = dec._prefill_rows_impl(w, ids, t0, flash=True)
    np.testing.assert_allclose(_f32(got[1]), _f32(want[1]),
                               atol=2e-3, rtol=2e-3)
    assert (_f32(got[1]).argmax(-1) == _f32(want[1]).argmax(-1)).all()
    first_kv = True
    for kept_g, kept_w in zip(got[0], want[0]):
        if not isinstance(kept_g, tuple):           # a conv layer's state
            np.testing.assert_allclose(_f32(kept_g), _f32(kept_w),
                                       atol=2e-3, rtol=2e-3)
            continue
        for a, b in zip(kept_g, kept_w):
            for row, n in enumerate(np.asarray(t0)):
                a_r, b_r = _f32(a[row, :, :n]), _f32(b[row, :, :n])
                if first_kv and dec.cache_spec().layers[0] == "kv":
                    assert np.array_equal(a_r, b_r)
                else:
                    np.testing.assert_allclose(a_r, b_r, atol=2e-3,
                                               rtol=2e-3)
            assert np.isfinite(_f32(a)).all()
        first_kv = False
    for a, b in zip(got[2:], want[2:]):             # expert rows
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_offline_generate_through_kernel_matches_dense(interpreted,
                                                      monkeypatch):
    """``generate`` decides the prefill's attention from where its
    weights live; steered to the kernel it emits the dense path's
    tokens."""
    from mxnet_tpu.models import llama as llama_mod

    dec = _llama(128, max_len=512)
    ids = np.random.default_rng(3).integers(1, 250, size=(2, 200))
    want = dec.generate(ids, 4)
    seen = []
    real = fa.prefill_applicable

    def as_tpu(platform, mesh, hd, lp):
        seen.append((platform, mesh, hd, lp))
        return real("tpu", mesh, hd, lp)

    monkeypatch.setattr(llama_mod, "prefill_applicable", as_tpu)
    got = dec.generate(ids, 4)
    assert seen == [("cpu", None, 128, 256)]
    assert np.array_equal(got, want)


# --- what an engine and its lane log say -------------------------------------

def test_engine_says_which_attention_its_prefill_runs(interpreted,
                                                      monkeypatch):
    """On this machine every bucket is dense, in ``stats()`` and in
    every ``prefill.batch`` record; an engine built as a chip builds it
    says ``flash``, runs the buckets of 256 and longer through the
    kernel, keeps the short ones dense, and emits the same tokens."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerConfig
    from mxnet_tpu.telemetry import tracing

    net = llama_tiny(hidden_size=256, intermediate_size=256, num_heads=2,
                     num_kv_heads=1, num_layers=2, max_seq_len=256)
    net.initialize()
    cfg = ServerConfig(max_batch=2, max_length=256, min_length=32,
                       num_slots=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 250, size=n) for n in (20, 100, 200)]

    def serve():
        since = time.perf_counter()
        with serving.GenerativeServer(net, cfg) as srv:
            toks = [srv.generate(p, max_new_tokens=3) for p in prompts]
            stats = srv.stats()
        recs = tracing.lane_log("prefill.batch", since=since)
        return toks, stats, [(r["bucket"][1], r["prefill_attention"])
                             for r in recs]

    want, stats, ran = serve()
    assert stats["prefill_attention"] == "dense"
    assert ran == [(32, "dense"), (128, "dense"), (256, "dense")]

    real = fa.prefill_applicable
    monkeypatch.setattr(fa, "prefill_applicable",
                        lambda platform, *rest: real("tpu", *rest))
    got, stats, ran = serve()
    assert stats["prefill_attention"] == "flash"
    assert ran == [(32, "dense"), (128, "dense"), (256, "flash")]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # no mesh-placed engine takes the kernel, whatever the platform
    from jax.sharding import Mesh

    from mxnet_tpu.serving.generative import LlamaServingEngine

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    eng = LlamaServingEngine(net, max_len=256, num_slots=2, mesh=mesh)
    assert eng.prefill_attention == "dense"
    assert eng.prefill_attention_at(256) == "dense"

"""The trainer's kernels that a model with latent attention and routed
experts needs: the flash kernels with ``v`` narrower than ``q`` and ``k``
(forward, ``dq``, ``dkv``; causal, several tiles) against ``_sdpa_ref``'s
gradients, and the grouped expert feed-forward's backward in Pallas
interpret mode against the gradients XLA takes of the every-expert form."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.models import moe
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import grouped_ffn

rs = np.random.RandomState(7)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# --- flash attention at two head widths ------------------------------------------

@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("t,block", [(256, (128, 128)), (128, (128, 128)),
                                     (512, (128, 256)), (512, (256, 128))],
                         ids=["four_tiles", "one_tile", "wide_keys",
                              "tall_queries"])
def test_flash_kernels_with_a_narrower_v(causal, t, block):
    """q and k 24 wide, v 16: the Pallas forward (with its log-sum-exp), dq
    and dkv in interpret mode, at square tiles and at ``block_q`` and
    ``block_k`` apart both ways (the diagonal then crosses tiles off their
    corners, and a dead tile's step asks for another block than its own),
    and the chunked ``jax.numpy`` fall-backs."""
    b, h, d, dv = 2, 3, 24, 16
    q, k = (jnp.asarray(rs.randn(b, h, t, d), jnp.float32) for _ in "qk")
    v = jnp.asarray(rs.randn(b, h, t, dv), jnp.float32)
    do = jnp.asarray(rs.randn(b, h, t, dv), jnp.float32)
    scale = d ** -0.5
    want, vjp = jax.vjp(lambda q, k, v: fa._sdpa_ref(q, k, v, causal, scale),
                        q, k, v)
    grads = vjp(do)
    o, lse = fa._fa_forward_pallas(q, k, v, causal, scale, *block,
                                   with_lse=True, interpret=True)
    assert o.shape == (b, h, t, dv) and lse.shape == (b, h, t)
    _close(o, want, 2e-5)
    got = fa._fa_backward_pallas(q, k, v, o, do, lse, causal, scale, *block,
                                 interpret=True)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    for g, w in zip(got, grads):
        _close(g, w, 5e-5)
    # the fall-backs a CPU run takes
    _close(fa._fa_forward_chunked(q, k, v, causal, scale, block=block[1]),
           want, 2e-5)
    for g, w in zip(fa._fa_backward(q, k, v, want, do, causal, scale,
                                    block=block[1]), grads):
        _close(g, w, 5e-5)
    # and the entry, differentiated
    out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_raw(
        q, k, v, causal, scale), q, k, v)
    _close(out, want, 2e-5)
    for g, w in zip(vjp(do), grads):
        _close(g, w, 5e-5)


@pytest.mark.parametrize("t", [1024, 2048, 4096])
def test_the_rules_tiles_divide_the_sequence_and_fit(t):
    """``joyai_flash.pretrain_s4k``'s heads (192 / 128, bf16, causal) at its
    length and the two below it: each kernel's tiles divide the sequence, fit
    the VMEM a step may ask for, and are the ones the sweep found."""
    tiles = {kern: fa.train_blocks(kern, t, t, 192, 128, 2, True)
             for kern in ("fwd", "dq", "dkv")}
    for kern, (bq, bk) in tiles.items():
        assert t % bq == 0 and t % bk == 0
        assert fa.train_row_bytes(bq, bk, 192, 2, kern, 128) \
            <= fa.TRAIN_VMEM_BYTES
    # the backward under the mask goes wide only where four tiles of 1,024
    # span the sequence, and dkv's VMEM count refuses them at these heads
    assert tiles == {"fwd": (1024, 1024), "dkv": (512, 512),
                     "dq": (1024, 1024) if t == 4096 else (512, 512)}
    # heads of 256 at tiles of 1,024: Mosaic refuses dq and dkv, so must
    # the rule
    assert fa.train_blocks("dq", 4096, 4096, 256, 256, 2, True) == (512, 512)
    # no mask, no tile half masked: the backward goes wide at every length
    assert fa.train_blocks("dkv", t, t, 128, 128, 2, False) == (1024, 1024)


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
def test_a_causal_grid_past_one_tile_is_the_list_of_its_live_steps(bq, bk):
    """No step of the three kernels lies above the causal diagonal: their
    grid is ``_live_steps``' list, which holds every pair ``_tile_runs``
    and no other, in the order the kernel sweeps (k tiles inside for the
    forward and ``dq``, q tiles inside for ``dkv``), and the gauges count
    it; without the mask the grid is every pair."""
    t = 512
    nq, nk = t // bq, t // bk

    def runs(i, j):
        return bool(fa._tile_runs(i, j, None, block_q=bq, block_k=bk,
                                  causal=True))

    qs, ks = fa._live_steps(nq, nk, bq, bk)
    assert list(zip(qs, ks)) == [(i, j) for i in range(nq)
                                 for j in range(nk) if runs(i, j)]
    ks, qs = fa._live_steps(nq, nk, bq, bk, q_outside=False)
    assert list(zip(ks, qs)) == [(i, j) for i in range(nk)
                                 for j in range(nq) if runs(j, i)]
    live = len(qs)
    assert 0 < live < nq * nk

    q, k, v, do = (jnp.asarray(rs.randn(1, 1, t, 8), jnp.float32)
                   for _ in range(4))
    for causal, steps in ((True, live), (False, nq * nk)):
        telemetry.enable()
        try:
            o, lse = fa._fa_forward_pallas(q, k, v, causal, 0.5, bq, bk,
                                           with_lse=True, interpret=True)
            fa._fa_backward_pallas(q, k, v, o, do, lse, causal, 0.5, bq, bk,
                                   interpret=True)
            gauges = telemetry.gauges()
        finally:
            telemetry.disable()
        for kern in ("fwd", "dq", "dkv"):
            assert gauges[f"flash.grid_steps.{kern}"] == steps
            assert gauges[f"flash.live_steps.{kern}"] == steps
            assert gauges[f"flash.rows_per_step.{kern}"] == 1


#: sha256 (16 hex digits) of the jaxpr (source locations taken out) of the
#: differentiated ``flash_attention_raw`` with the Pallas kernels forced, on
#: the commit before ``v`` got a width of its own (PR 43, 5a061cd): at equal
#: widths the one-tile kernels are what they were, grid, blocks and bodies.
#: Past one tile PR 45 changed the program on purpose (tiles from
#: ``train_blocks``, the causal grid a list of its live steps, no guard on
#: ``lse``, the backward's statistics along lanes) and the second hash is
#: that program's.
PARENT_FLASH = {
    ((128, 12, 128, 64), False): "414ec3460b14916a",    # bert_base.pretrain_s128
    ((2, 8, 1024, 64), True): "37376d5ea75768da",       # past one tile, causal
}


@pytest.mark.parametrize("shape,causal", list(PARENT_FLASH))
def test_equal_widths_trace_the_kernels_of_the_parent(monkeypatch, shape,
                                                      causal):
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention_raw(q, k, v, causal, 0.125) \
            .astype(jnp.float32).sum()

    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_FLASH[shape, causal]
    assert fa.train_tiles(shape[0] * shape[1], shape[2], shape[2], shape[3]) \
        == (16 if shape[2] == 128 else 1)


def test_train_form_names_the_tiles(monkeypatch):
    assert fa.train_form((4, 32, 4096, 192), 128) == "chunked"   # a CPU
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    assert fa.train_form((4, 32, 4096, 192), 128, causal=True) \
        == "pallas:fwd1024x1024,dq1024x1024,dkv512x512:d192/128:hb1"
    assert fa.train_form((128, 12, 128, 64)) \
        == "pallas:fwd128x128,dq128x128,dkv128x128:d64/64:hb16"


# --- the token-major entry: (B, T, N x H) where it lies ---------------------------

def _flat(x):           # (B, T, N, H) -> (B, T, N x H), the projections' own
    return x.reshape(*x.shape[:2], -1)


def _tr(x):             # (B, T, N, H) <-> (B, N, T, H), by hand
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape,bb,dtype", [
    ((4, 128, 12, 64), None, "float32"), ((4, 128, 12, 64), 1, "float32"),
    ((4, 128, 12, 64), None, "bfloat16"), ((4, 256, 2, 128), None, "float32"),
    ((4, 256, 4, 64), 2, "float32"), ((2, 512, 2, 64), None, "float32"),
    ((2, 512, 2, 64), 2, "float32"), ((2, 128, 2, 256), None, "float32"),
], ids=["bert_heads", "bert_heads_a_row_a_step", "bert_heads_bf16",
        "t256_hd128", "t256_two_rows", "t512", "t512_two_rows", "hd256"])
def test_token_major_kernels_match_the_dense_vjp_and_the_head_major_bits(
        monkeypatch, shape, bb, dtype, causal):
    """Forward, ``dq`` and ``dkv`` on ``(B, T, N x H)`` in interpret mode:
    BERT's heads (12 of 64, two to a lane tile) reduced in ``B``, heads of
    128 and 256 (one a block), sequences of 256 and 512, at the batch rows
    a step that ``tokens_rows`` gives and at others.  Against
    ``_sdpa_ref``'s vjp; and against the head-major kernels on the same
    values transposed by hand: the forward and ``lse`` to the bit (a head's
    arithmetic is the same: the other head's lanes enter its products as
    zeros), the gradients within 2^-20 of the tensor's largest value in
    float32 (``delta`` is summed inside the kernels, over a tile's lanes
    under a mask, not by XLA over a head's 64) and a bf16 rounding in
    bf16.  ``lse`` lies along lanes, ``(B, N / pair, pair, T)``."""
    b, t, n, h = shape
    lanes, pair = fa.tokens_lanes(h)
    if bb is not None:
        monkeypatch.setattr(fa, "tokens_rows", lambda *_: bb)
    q, k, v, do = (jnp.asarray(rs.randn(*shape), jnp.float32)
                   .astype(dtype) for _ in range(4))
    scale = h ** -0.5
    o, lse = fa._fa_forward_tokens(_flat(q), _flat(k), _flat(v), n, causal,
                                   scale, with_lse=True, interpret=True)
    assert o.shape == (b, t, n * h) and o.dtype == q.dtype
    assert lse.shape == (b, n // pair, pair, t) and lse.dtype == jnp.float32
    alone = fa._fa_forward_tokens(_flat(q), _flat(k), _flat(v), n, causal,
                                  scale, interpret=True)
    assert np.array_equal(alone, o)
    got = fa._fa_backward_tokens(_flat(q), _flat(k), _flat(v), o, _flat(do),
                                 lse, n, causal, scale, interpret=True)
    assert [g.shape for g in got] == [(b, t, n * h)] * 3
    assert [g.dtype for g in got] == [q.dtype] * 3

    o2, lse2 = fa._fa_forward_pallas(_tr(q), _tr(k), _tr(v), causal, scale,
                                     with_lse=True, interpret=True)
    got2 = fa._fa_backward_pallas(_tr(q), _tr(k), _tr(v), o2, _tr(do), lse2,
                                  causal, scale, interpret=True)
    assert np.array_equal(o.reshape(shape), _tr(o2))
    assert np.array_equal(lse.reshape(b, n, t), lse2)
    f32 = dtype == "float32"
    for g, g2 in zip(got, got2):
        _close(g.reshape(shape), _tr(g2), 2.0 ** -20 if f32 else 2.0 ** -7)

    want, vjp = jax.vjp(
        lambda q, k, v: fa._sdpa_ref(q, k, v, causal, scale),
        *(_tr(x).astype(jnp.float32) for x in (q, k, v)))
    _close(o.reshape(shape), _tr(want), 2e-5 if f32 else 2.0 ** -7)
    for g, w in zip(got, vjp(_tr(do).astype(jnp.float32))):
        _close(g.reshape(shape), _tr(w), 5e-5 if f32 else 2.0 ** -5)


def _interpreted(monkeypatch):
    """``sdpa_raw`` as on a chip, its Pallas calls in interpret mode."""
    import functools

    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    for name in ("_fa_forward_tokens", "_fa_backward_tokens"):
        monkeypatch.setattr(fa, name, functools.partial(
            getattr(fa, name), interpret=True))


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_sdpa_raw_differentiates_through_the_token_major_entry(monkeypatch,
                                                               causal):
    """The model's call, ``sdpa_raw`` on ``(B, T, N, H)``, and ``jax.vjp``
    of it against XLA's dense attention; the gauges of the three grids say
    whose they are."""
    from mxnet_tpu.ops.attention import sdpa_raw

    _interpreted(monkeypatch)
    shape = (4, 128, 4, 64)
    q, k, v, do = (jnp.asarray(rs.randn(*shape), jnp.float32)
                   for _ in range(4))
    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        out, vjp = jax.vjp(lambda q, k, v: sdpa_raw(q, k, v, causal=causal),
                           q, k, v)
        got = vjp(do)
        gauges = telemetry.gauges()
    finally:
        if not was:
            telemetry.disable()
    want, vjp = jax.vjp(lambda q, k, v: jax.nn.dot_product_attention(
        q, k, v, is_causal=causal), q, k, v)
    _close(out, want, 2e-5)
    for g, w in zip(got, vjp(do)):
        _close(g, w, 5e-5)
    for kern in ("fwd", "dq", "dkv"):
        assert gauges[f"flash.token_major.{kern}"] == 1
        assert gauges[f"flash.rows_per_step.{kern}"] == 8     # 4 x 2 heads
        assert gauges[f"flash.grid_steps.{kern}"] == 1
        assert gauges[f"flash.live_steps.{kern}"] == 1
    # not differentiated: the forward alone, no lse
    _close(sdpa_raw(q, k, v, causal=causal), want, 2e-5)


#: what ``sdpa_raw`` traces for operands (B, T, N, H): ``tokens`` (the
#: token-major kernels, no transpose), ``heads`` (transposes around
#: ``flash_attention_raw``'s Pallas kernels: the parent's program),
#: ``chunked`` (transposes around its ``jax.numpy`` fall-back) or ``xla``
#: (``jax.nn.dot_product_attention``)
RULE = {
    "bert_base": (dict(shape=(8, 128, 12, 64)), "tokens"),
    "heads_of_128": (dict(shape=(8, 256, 4, 128)), "tokens"),
    "heads_of_256": (dict(shape=(2, 384, 2, 256)), "tokens"),
    "one_tile_of_512": (dict(shape=(2, 512, 2, 64)), "tokens"),
    "causal": (dict(shape=(8, 128, 12, 64), causal=True), "tokens"),
    "an_odd_number_of_heads_of_64": (dict(shape=(8, 128, 3, 64)), "heads"),
    "heads_of_192": (dict(shape=(2, 128, 4, 192)), "heads"),
    "heads_of_32": (dict(shape=(2, 128, 4, 32)), "heads"),
    "past_one_tile": (dict(shape=(2, 1024, 8, 64)), "heads"),
    "a_mesh": (dict(shape=(8, 128, 12, 64), mesh=True), "heads"),
    "a_mask": (dict(shape=(8, 128, 12, 64), mask=True), "xla"),
    "a_length_off_the_tile": (dict(shape=(8, 120, 12, 64)), "xla"),
    "fewer_kv_heads": (dict(shape=(8, 128, 12, 64), kv_heads=4), "xla"),
    "MXT_PALLAS_FLASH=0": (dict(shape=(8, 128, 12, 64),
                                env={"MXT_PALLAS_FLASH": "0"}), "chunked"),
    "MXT_PALLAS_FLASH_BWD=0": (dict(shape=(8, 128, 12, 64),
                                    env={"MXT_PALLAS_FLASH_BWD": "0"}),
                               "heads"),
    "a_cpu": (dict(shape=(8, 128, 12, 64),
                   env={"MXT_FORCE_PALLAS_FLASH": "0"}), "xla"),
}


@pytest.mark.parametrize("case", list(RULE))
def test_the_rule_is_the_operands_own_shape(monkeypatch, case):
    """``sdpa_raw`` takes the token-major entry where the mask is absent,
    heads are equal and fill lane tiles (64 wide and an even number, or a
    multiple of 128), the sequence is one tile of a multiple of 128, the
    platform is a TPU and no mesh is set; every other call traces what it
    traced before, under the switches it had."""
    from mxnet_tpu import parallel
    from mxnet_tpu.ops.attention import sdpa_raw

    given, want = RULE[case]
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    for name, value in given.get("env", {}).items():
        monkeypatch.setenv(name, value)
    b, t, n, h = given["shape"]
    q = jax.ShapeDtypeStruct(given["shape"], jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, given.get("kv_heads", n), h),
                              jnp.bfloat16)
    mask = (jax.ShapeDtypeStruct((b, 1, t, t), jnp.bool_),) \
        if given.get("mask") else ()

    def loss(q, k, v, *m):
        return sdpa_raw(q, k, v, *m, causal=given.get("causal", False)) \
            .astype(jnp.float32).sum()

    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        for kern in ("fwd", "dq", "dkv"):
            telemetry._gauges.pop(f"flash.token_major.{kern}", None)
        if given.get("mesh"):
            parallel.set_mesh(parallel.make_mesh({"dp": 4, "tp": 2}))
        with jax.enable_x64(False):
            text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
                q, kv, kv, *mask))
        gauges = telemetry.gauges()
        form = fa.train_form(given["shape"], layout="tokens")
    finally:
        parallel.set_mesh(None)
        if not was:
            telemetry.disable()
    said = [gauges.get(f"flash.token_major.{kern}")
            for kern in ("fwd", "dq", "dkv")]
    calls = text.count("pallas_call")
    if want == "tokens":
        assert said == [1, 1, 1] and calls == 3
        assert "transpose[" not in text
        assert f"bf16[{b},{t},{n * h}]" in text
    elif want == "heads":
        # MXT_PALLAS_FLASH_BWD=0: the forward's kernel, a chunked backward
        assert calls == (1 if "env" in given else 3)
        assert said == [0] + [0 if calls == 3 else None] * 2
        assert "transpose[" in text
    else:
        assert calls == 0 and said == [None] * 3
        assert ("transpose[" in text and "scan" in text) \
            == (want == "chunked")
    # the word for a log says the same of the shape (it sees no mask and
    # no second head count)
    assert form.endswith(":tokens") == (
        want == "tokens" or case in ("a_mask", "fewer_kv_heads"))


def test_train_form_names_the_layout(monkeypatch):
    bert = (128, 128, 12, 64)               # (B, T, N, H), as sdpa_raw has it
    assert fa.train_form(bert, layout="tokens") == "chunked"       # a CPU
    monkeypatch.setenv("MXT_FORCE_PALLAS_FLASH", "1")
    assert fa.train_form(bert, layout="tokens") \
        == "pallas:fwd128x128,dq128x128,dkv128x128:d64/64:hb16:tokens"
    assert fa.train_form((16, 512, 8, 128), layout="tokens") \
        == "pallas:fwd512x512,dq512x512,dkv512x512:d128/128:hb2:tokens"
    # heads of 192: the head-major word of the transposed shape
    assert fa.train_form((4, 4096, 32, 192), 128, causal=True,
                         layout="tokens") \
        == fa.train_form((4, 32, 4096, 192), 128, causal=True)
    assert fa.train_form((128, 128, 11, 64), layout="tokens") \
        == fa.train_form((128, 11, 128, 64))


@pytest.mark.parametrize("b,pair,t,d,itemsize,want", [
    (128, 2, 128, 64, 2, 8), (128, 2, 128, 64, 4, 4), (4, 2, 128, 64, 2, 4),
    (6, 2, 128, 64, 2, 6), (128, 1, 128, 128, 2, 16), (16, 1, 256, 128, 2, 4),
    (16, 2, 512, 64, 2, 1), (16, 1, 512, 128, 2, 2), (7, 1, 384, 256, 2, 1),
], ids=["bert_base", "bert_base_f32", "a_small_batch", "a_batch_of_6",
        "heads_of_128", "t256", "t512_heads_of_64", "t512_heads_of_128",
        "t384_heads_of_256"])
def test_a_token_major_step_holds_the_rows_the_head_major_one_does(
        b, pair, t, d, itemsize, want):
    """``tokens_rows``: batch rows a step, a divisor of the batch, whose
    ``pair`` heads each count a (batch, head) row of ``train_tiles``'
    VMEM rule (16 rows at BERT's shape: 8 batch rows of a lane tile's two
    heads)."""
    bb = fa.tokens_rows(b, pair, t, t, d, itemsize)
    assert bb == want and b % bb == 0
    assert bb * pair * fa.train_row_bytes(t, t, d, itemsize) \
        <= fa.TRAIN_VMEM_BYTES or bb == 1
    assert bb * pair <= fa.train_tiles(b * pair * 64, t, t, d, itemsize) \
        or bb == 1


# --- the grouped expert feed-forward's backward -------------------------------

def _case(n, h, i, e, first, held, k, skew=True, part_live=True):
    x = jnp.asarray(rs.randn(n, h), jnp.float32)
    bank = [jnp.asarray(rs.randn(*s) * 0.1, jnp.float32)
            for s in ((held, h, i), (held, h, i), (held, i, h))]
    logits = rs.randn(n, e)
    if skew:
        logits[:, first + 1] += 3.0          # most rows take this one
        logits[:, first + held - 2] -= 100.0    # and this one gets no row
    idx = jnp.asarray(np.argsort(-logits, axis=1)[:, :k].astype(np.int32))
    w = jnp.asarray(rs.rand(n, k), jnp.float32)
    live = jnp.asarray(rs.rand(n) > 0.1) if part_live else None
    return x, idx - first, w, bank, live


def _every_expert(x, idx, w, bank, live):
    """The other form of ``routed_ffn``, written out: what XLA differentiates."""
    n, held = x.shape[0], bank[0].shape[0]
    there = (idx >= 0) & (idx < held)
    if live is not None:
        there = there & live[:, None]
    comb = jnp.zeros((n, held + 1)).at[
        jnp.arange(n)[:, None], jnp.where(there, idx, held)].add(
            jnp.where(there, w, 0.0))[:, :held]
    g = jnp.einsum("nh,ehi->nei", x, bank[0])
    u = jnp.einsum("nh,ehi->nei", x, bank[1])
    act = g * jax.nn.sigmoid(g) * u * comb[:, :, None]
    return jnp.einsum("nei,eih->nh", act, bank[2])


def _sorted_pairs(idx, live, held):
    """The kernels' listing, in numpy: (expert, row) of every held pair,
    expert by expert and row by row."""
    there = np.asarray((idx >= 0) & (idx < held))
    if live is not None:
        there = there & np.asarray(live)[:, None]
    rows, cols = np.nonzero(there)
    experts = np.asarray(idx)[rows, cols]
    order = np.lexsort((rows, experts))
    return experts[order], rows[order]


@pytest.mark.parametrize("n,window,token_tile,dead", [
    (300, None, None, None), (300, 256, None, None), (129, 128, None, None),
    (300, 256, 64, None), (300, 256, 64, (64, 128)), (129, 128, 64, None),
    (300, 128, 128, (0, 128))],
    ids=["one_window_forward", "windows_of_256", "rows_off_the_tile",
         "token_tiles_of_64", "a_token_tile_without_a_pair",
         "rows_off_the_token_tile", "the_first_token_tile_dead"])
def test_grouped_backward_matches_every_expert(n, window, token_tile, dead):
    """Skewed routing, an expert with no row, rows not a multiple of the
    tile, a held part (6 of 16 from the 4th) of the router, rows no request
    owns: dX, the three banks' gradients and the combine weights'.  With
    token tiles: a row held by several experts inside one window and across
    two, an expert's group cut by a window's edge, the last window's unused
    rest, a token tile no pair falls in (``dead`` rows no request owns), a
    last token tile that is part rows."""
    x, idx, w, bank, live = _case(n, 128, 128, 16, 3, 6, 4)
    if dead:
        live = live & ~((jnp.arange(n) >= dead[0]) & (jnp.arange(n) < dead[1]))
    dy = jnp.asarray(rs.randn(n, 128), jnp.float32)

    def ker(x, w, *bank):
        return grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, live, window=window, token_tile=token_tile,
            interpret=True)

    def ref(x, w, *bank):
        return _every_expert(x, idx, w, bank, live)

    _close(ker(x, w, *bank), ref(x, w, *bank), 1e-5)
    got = jax.grad(lambda *a: (ker(*a) * dy).sum(), argnums=range(5))(
        x, w, *bank)
    want = jax.grad(lambda *a: (ref(*a) * dy).sum(), argnums=range(5))(
        x, w, *bank)
    for g, r in zip(got, want):
        _close(g, r, 2e-5)
    # the expert without a row: zeros, not what the buffer held
    empty = 6 - 2
    assert not np.asarray(got[2][empty]).any()
    assert not np.asarray(got[4][empty]).any()
    # a pair on another chip's expert, or of a row nobody owns, has no say
    there = np.asarray((idx >= 0) & (idx < 6) & live[:, None])
    assert not np.asarray(got[1])[~there].any()
    if token_tile and window:
        # what the case is there to meet, said of its own listing
        experts, rows = _sorted_pairs(idx, live, 6)
        wins = np.arange(len(rows)) // window
        assert len(rows) > window and len(rows) % window
        twice = [wins[rows == r] for r in np.unique(rows)
                 if (rows == r).sum() > 1]
        assert any(len(set(t)) < len(t) for t in twice)   # inside one window
        assert any(len(set(t)) > 1 for t in twice)        # across two
        assert any(len(set(wins[experts == e])) > 1 for e in range(6))
        tiles = set(range(-(-n // token_tile)))
        assert (tiles - set(rows // token_tile) != set()) == bool(dead)
        assert n % token_tile


#: the four served banks whose 512-row calls fit one window, cut down in
#: rows and to widths of a lane tile or two (the published widths run on
#: the chip, ``tests_tpu/test_grouped_ffn_tpu.py``, and compile for a v5e
#: in ``tests/test_paged_attention.py``): the router's experts, the part
#: held here, experts a row, the router's width, the experts' (a latent
#: layer's differ), an expert's width, the scores, the expert's kind
SERVED = {
    "sdar_30b": dict(e=128, held=(0, 128), k=8, wide=128, h=128, i=128,
                     score="softmax", kind="swiglu"),
    "lfm2_24b": dict(e=64, held=(0, 64), k=4, wide=128, h=128, i=256,
                     score="sigmoid", kind="swiglu"),
    "qwen3_next": dict(e=512, held=(128, 128), k=10, wide=128, h=128, i=128,
                       score="softmax", kind="swiglu"),
    "nemotron3_super": dict(e=512, held=(384, 128), k=22, wide=256, h=128,
                            i=256, score="sigmoid", kind="relu2"),
}


def _plain(x, idx, w, bank, live, kind):
    """A row at a time, a pair at a time, float64."""
    held = bank[1].shape[0]
    wg, wu, wd = (None if a is None else np.asarray(a, np.float64)
                  for a in bank)
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    for r in range(x.shape[0]):
        for e, we in zip(np.asarray(idx)[r], np.asarray(w, np.float64)[r]):
            if not live[r] or not 0 <= e < held:
                continue
            if kind == "relu2":
                act = np.maximum(x[r] @ wu[e], 0.0) ** 2
            else:
                g = x[r] @ wg[e]
                act = g / (1.0 + np.exp(-g)) * (x[r] @ wu[e])
            out[r] += we * (act @ wd[e])
    return out


@pytest.mark.parametrize("routing", [
    "even", "one_expert_on_every_row", "an_expert_without_a_row",
    "dead_rows", "pairs_off_the_row_tile"])
@pytest.mark.parametrize("bank", sorted(SERVED))
def test_the_resident_form_matches_every_expert_and_a_plain_sum(bank,
                                                                routing):
    """A call whose pairs fit one window (the rows and their float32 sum
    in VMEM, ``grouped_expert_ffn_resident``) against ``routed_ffn``'s
    ``every_expert`` form, which a CPU takes, and against a plain float64
    sum a pair at a time: 64 rows whose ``N x k`` pairs are whole row
    tiles, and 45 whose are not; an expert every row holds (its group
    runs over tiles), one nobody holds, rows no request owns."""
    b = SERVED[bank]
    n = 45 if routing == "pairs_off_the_row_tile" else 64
    first, held = b["held"]
    relu2 = b["kind"] == "relu2"
    x = jnp.asarray(rs.randn(n, b["wide"]), jnp.float32)
    lat = jnp.asarray(rs.randn(n, b["h"]), jnp.float32) \
        if b["h"] != b["wide"] else None
    rw = jnp.asarray(rs.randn(b["e"], b["wide"]) * 0.3, jnp.float32)
    wg, wu, wd = (jnp.asarray(rs.randn(*s) * 0.1, jnp.float32) for s in (
        (held, b["h"], b["i"]), (held, b["h"], b["i"]),
        (held, b["i"], b["h"])))
    wg = None if relu2 else wg
    bias = np.zeros(b["e"], np.float32)
    if routing == "one_expert_on_every_row":
        bias[first + 3] = 100.0
    if routing == "an_expert_without_a_row":
        bias[first + held - 2] = -100.0
    live = rs.rand(n) > 0.3 if routing == "dead_rows" else np.ones(n, bool)
    assert grouped_ffn.rows_form(n, b["k"], b["h"], b["i"], 4) == "resident"
    assert moe.expert_product(n, b["k"], held, b["h"], b["i"],
                              jnp.float32) == "every_expert"
    every, counts = moe.routed_ffn(
        x, rw, wg, wu, wd, b["k"], score=b["score"],
        choice_bias=jnp.asarray(bias), experts_held=b["held"],
        live=jnp.asarray(live), kind=b["kind"], rows=lat)
    idx, w = moe.route(x, rw, b["k"], b["score"], jnp.asarray(bias))
    rows = x if lat is None else lat
    got = grouped_ffn.grouped_expert_ffn(
        rows, idx - first, w, wg, wu, wd, jnp.asarray(live), interpret=True,
        kind=b["kind"])
    assert got.shape == rows.shape and got.dtype == rows.dtype
    # what the case is there to meet, said of its own routing
    here = np.asarray(counts)[first:first + held]
    assert (n * b["k"] % grouped_ffn.ROW_TILE != 0) \
        == (routing == "pairs_off_the_row_tile")
    assert (here[3] == n) == (routing == "one_expert_on_every_row")
    assert (here[held - 2] == 0) == (routing == "an_expert_without_a_row") \
        or bank != "sdar_30b"
    assert 0 < here.sum() <= live.sum() * b["k"]
    # (a row no request owns: zero here, anything there, nobody reads it)
    _close(np.asarray(got)[live], np.asarray(every)[live], 2e-5)
    _close(got, _plain(rows, np.asarray(idx) - first, w, (wg, wu, wd), live,
                       b["kind"]), 2e-5)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("sizes", [
    [3, 0, 8, 9, 17, 0, 1], [0, 0, 40], [0, 0, 0], [8, 8, 8, 8]],
    ids=["mixed", "one_long_group", "no_pair_held", "whole_tiles"])
def test_a_resident_visit_is_one_experts_pairs_wherever_they_lie(sizes):
    """``_own_visits``: every listed pair in exactly one visit, a visit
    one expert's and ``tm`` pairs at most, a group of up to ``tm`` pairs
    one visit however it lies across the list's row tiles (the windowed
    form's ``_visits`` gives it one for each tile it touches)."""
    tm, held = 8, len(sizes)
    m = sum(sizes)
    key = np.repeat(np.arange(held), sizes)
    key = np.concatenate([key, np.full(-(-(m + 5) // tm) * tm - m, held)])
    eid, first, hi, total = (np.asarray(a) for a in grouped_ffn._own_visits(
        jnp.asarray(key, jnp.int32), held, tm))
    total = int(total[0])
    assert total == sum(-(-s // tm) for s in sizes)
    assert total <= int(grouped_ffn._visits(
        jnp.asarray(key, jnp.int32), held, tm)[4][0])
    assert len(eid) == len(key) // tm + held
    pairs = [np.arange(first[v], hi[v]) for v in range(total)]
    assert all(0 < len(p) <= tm for p in pairs)
    assert all((key[p] == eid[v]).all() for v, p in enumerate(pairs))
    assert (np.concatenate(pairs or [np.arange(0)]) == np.arange(m)).all()
    # the static rest repeats the last visit: no new block is fetched
    assert (eid[total:] == eid[max(total - 1, 0)]).all()


def test_the_resident_form_in_bfloat16_sums_in_float32():
    """The rows wait in VMEM as float32 (a one-row slice of packed bf16
    is half a sublane) and are products' operands as bf16 again, exactly;
    the sum over a row's ``k`` stays float32 to the one cast."""
    b = SERVED["sdar_30b"]
    n, held = 64, 128
    x = jnp.asarray(rs.randn(n, b["h"]), jnp.bfloat16)
    bank = [jnp.asarray(rs.randn(*s) * 0.1, jnp.bfloat16) for s in (
        (held, b["h"], b["i"]), (held, b["h"], b["i"]), (held, b["i"], b["h"]))]
    idx = jnp.asarray(np.argsort(-rs.randn(n, held), axis=1)[:, :8]
                      .astype(np.int32))
    w = jnp.asarray(rs.rand(n, 8), jnp.float32)
    got = grouped_ffn.grouped_expert_ffn(x, idx, w, *bank, interpret=True)
    assert got.dtype == jnp.bfloat16
    # float64 from the same bf16 values: the kernel rounds its
    # activations to bf16 once and its result once
    want = _plain(x.astype(jnp.float32), idx, w,
                  [a.astype(jnp.float32) for a in bank], np.ones(n, bool),
                  "swiglu")
    _close(got.astype(jnp.float32), want, 2.0 ** -6)


@pytest.mark.parametrize("rows,k,h,i,want", [
    (512, 8, 2048, 768, "resident"), (512, 4, 2048, 1536, "resident"),
    (512, 10, 2048, 512, "resident"), (512, 22, 1024, 2688, "resident"),
    (2048, 8, 2048, 768, "resident"),     # 16,384 pairs: the last window
    (2304, 8, 2048, 768, "kernel"),       # the pairs exceed a window
    (16384, 8, 2048, 768, "kernel"), (32768, 8, 6144, 2048, "kernel"),
    # one window, but an expert walked in width tiles: the tiles fill the cap
    (512, 8, 6144, 2048, "kernel"), (512, 2, 4096, 14336, "kernel"),
], ids=["sdar_30b", "lfm2_24b", "qwen3_next", "nemotron3_super",
        "a_full_window", "past_a_window", "joyai_flash_trained",
        "glm5_long_prefill", "glm5_512_rows", "mixtral_512_rows"])
def test_the_rule_of_shapes_picks_where_the_rows_cross(rows, k, h, i, want):
    """``rows_form``: in VMEM where every pair fits one window and the
    rows, their float32 sum and the kernel's blocks fit the cap, else a
    window at a time through ``grouped_expert_ffn_rows``."""
    assert grouped_ffn.rows_form(rows, k, h, i) == want
    tm, wt = grouped_ffn.tiles(h, i)
    one = -(-rows * k // tm) * tm <= grouped_ffn.window_pairs(rows, k, h, tm)
    fits = grouped_ffn._resident_vmem_bytes(rows, h, wt, 2, tm, wt < i) \
        <= grouped_ffn._VMEM_CAP
    assert (want == "resident") == (one and fits)


#: sha256 (16 hex digits) of the jaxprs (source locations taken out) of the
#: windowed form on the commit before the resident form (PR 47, 8efc484):
#: the trained layer's forward and its gradients, the long prefill's
#: forward.  Their programs are the parent's to the letter.
PARENT_WINDOWED = {
    "joyai_flash_trained": ((16384, 8, 16, 2048, 768), "b6c120d36cdf064e"),
    "joyai_flash_trained_grad": ((16384, 8, 16, 2048, 768),
                                 "2259e0de4f2df175"),
    "glm5_long_prefill": ((32768, 8, 16, 6144, 2048), "08a97c9d88b6cdfc"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_WINDOWED))
def test_the_windowed_calls_are_the_parents_programs(cell):
    """The calls whose pairs exceed a window (``joyai_flash.pretrain_s4k``,
    ``glm5.longdoc_prefill``) are not this PR's."""
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    (rows, k, held, h, i), want = PARENT_WINDOWED[cell]
    avals = (sds((rows, h), bf), sds((rows, k), jnp.int32),
             sds((rows, k), jnp.float32), sds((held, h, i), bf),
             sds((held, h, i), bf), sds((held, i, h), bf))
    fn = grouped_ffn.grouped_expert_ffn
    if cell.endswith("_grad"):
        fn = jax.grad(lambda *a: grouped_ffn.grouped_expert_ffn(*a)
                      .astype(jnp.float32).sum(), argnums=(0, 2, 3, 4, 5))
    else:
        avals += (sds((rows,), jnp.bool_),)
    with jax.enable_x64(False):
        text = str(jax.make_jaxpr(fn)(*avals))
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert "grouped_expert_ffn_rows" in text
    assert "grouped_expert_ffn_resident" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("rows,k,held,h,i,want", [
    (16384, 8, 16, 2048, 768,
     dict(fwd=16384, form="kernel", token_tile=1024, row_tile=128)),
    (32768, 8, 16, 6144, 2048,
     dict(fwd=5376, form="kernel", token_tile=512, row_tile=None)),
    (512, 8, 128, 2048, 768,
     dict(fwd=4096, form="resident", token_tile=512, row_tile=128)),
], ids=["joyai_flash_trained", "glm5_long_prefill", "sdar_block_pass"])
def test_the_gauges_say_what_the_shapes_chose(rows, k, held, h, i, want):
    """Where the program is traced: the forward's and the backward's
    window, whether the rows cross in ``grouped_expert_ffn_rows`` or in
    VMEM inside the one kernel, and the token tile, at the three sparse
    cells' shapes."""
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    avals = (sds((rows, h), bf), sds((rows, k), jnp.int32),
             sds((rows, k), jnp.float32), sds((held, h, i), bf),
             sds((held, h, i), bf), sds((held, i, h), bf))

    def loss(x, idx, w, *bank):
        return grouped_ffn.grouped_expert_ffn(x, idx, w, *bank) \
            .astype(jnp.float32).sum()

    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        for name in ("fwd.window_pairs", "rows_form", "token_tile",
                     "bwd.row_tile", "bwd.window_pairs"):
            telemetry._gauges.pop("grouped_ffn." + name, None)
        fn = jax.grad(loss, argnums=(0, 2)) if want["row_tile"] else loss
        jax.make_jaxpr(fn)(*avals)
        got = telemetry.gauges()
    finally:
        if not was:
            telemetry.disable()
    assert got["grouped_ffn.fwd.window_pairs"] == want["fwd"]
    assert got["grouped_ffn.rows_form"] == want["form"]
    # (a resident forward sets none; its backward lists the held pairs)
    assert got.get("grouped_ffn.token_tile") == want["token_tile"]
    if want["row_tile"]:
        assert got["grouped_ffn.bwd.row_tile"] == want["row_tile"]
        assert got["grouped_ffn.bwd.window_pairs"] == want["fwd"]


def test_grouped_backward_in_bfloat16_accumulates_in_float32():
    x, idx, w, bank, live = _case(256, 128, 128, 8, 0, 8, 2, skew=False,
                                  part_live=False)
    dy = jnp.asarray(rs.randn(256, 128), jnp.float32)
    low = [a.astype(jnp.bfloat16) for a in (x, *bank)]

    def ker(x, *bank):
        return grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, interpret=True).astype(jnp.float32)

    def ref(x, *bank):
        return _every_expert(x, idx, w, bank, None)

    got = jax.grad(lambda *a: (ker(*a) * dy).sum(), argnums=range(4))(*low)
    want = jax.grad(lambda *a: (ref(*a) * dy).sum(), argnums=range(4))(
        *(a.astype(jnp.float32) for a in low))
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16
        _close(g.astype(jnp.float32), r, 3e-2)


def test_a_walked_expert_has_no_backward_yet():
    x, idx, w, bank, live = _case(256, 128, 256, 8, 0, 8, 2)
    with pytest.raises(NotImplementedError, match="width tiles"):
        jax.grad(lambda x: grouped_ffn.grouped_expert_ffn(
            x, idx, w, *bank, width_tile=128, interpret=True).sum())(x)


def test_routed_ffn_differentiates_in_the_form_expert_product_names():
    """On a CPU ``expert_product`` says ``every_expert`` and XLA takes the
    gradient; the router learns through the combine weights."""
    n, h, i, e = 64, 16, 8, 8
    x = jnp.asarray(rs.randn(n, h), jnp.float32)
    router = jnp.asarray(rs.randn(e, h), jnp.float32)
    bank = [jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
            for s in ((4, h, i), (4, h, i), (4, i, h))]
    assert moe.expert_product(n, 2, 4, h, i, jnp.float32) == "every_expert"
    g = jax.grad(lambda r: moe.routed_ffn(
        x, r, *bank, 2, score="sigmoid", experts_held=(2, 4))[0].sum())(router)
    assert np.abs(np.asarray(g)).max() > 0

"""Paged decode attention (``mxnet_tpu/ops/paged_attention.py``): the
Pallas kernel under the TPU interpreter against ``LlamaDecoder._attend``
on the gathered view, the step and verify programs through it, which
path an engine picks, and a compile of the kernel for the v5e at the two
benchmark cells' shapes (no chip: the described topology)."""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models.llama import LlamaDecoder, llama_tiny
from mxnet_tpu.ops import paged_attention as pa

BS, MB, NB, HD = 16, 12, 96, 128


def _interpret():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()


class _Cfg:
    """What ``_attend`` reads of a decoder's config."""

    def __init__(self, heads, kv_heads):
        self.num_heads, self.num_kv_heads, self.head_dim = \
            heads, kv_heads, HD


def _gathered(q, kp, vp, tables, lengths, heads, kv_heads):
    """The present step's attention: the clamped gather of every slot's
    whole view, then ``_attend`` under the per-slot (and, for a verify
    window, per-column) length mask.  q (S, H, hd) or (S, K, H, hd)."""
    s = q.shape[0]
    q4 = q[:, None] if q.ndim == 3 else q           # (S, K, H, hd)
    cols = q4.shape[1]
    gat = jnp.minimum(tables, kp.shape[0] - 1)
    kc = kp[gat].transpose(0, 2, 1, 3, 4).reshape(s, kv_heads, -1, HD)
    vc = vp[gat].transpose(0, 2, 1, 3, 4).reshape(s, kv_heads, -1, HD)
    bound = lengths[:, None] + jnp.arange(cols)[None, :]        # (S, K)
    mask = (jnp.arange(kc.shape[2])[None, None, :]
            < bound[:, :, None])[:, None]                       # (S,1,K,T)
    dec = LlamaDecoder.__new__(LlamaDecoder)
    dec.cfg = _Cfg(heads, kv_heads)
    out = dec._attend(q4.transpose(0, 2, 1, 3), kc, vc, mask)   # (S,H,K,hd)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


def _pool(rng, kv_heads):
    shape = (NB, kv_heads, BS, HD)
    return (jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16))


def _tables(rng, lengths, shuffled=True):
    """A table row per length: ``ceil(length / bs)`` blocks drawn from a
    shuffled (non-contiguous) or ascending pool order; 0 = vacant."""
    order = rng.permutation(NB) if shuffled else np.arange(NB)
    tables = np.full((len(lengths), MB), NB, np.int32)
    at = 0
    for s, n in enumerate(lengths):
        nblk = -(-int(n) // BS)
        tables[s, :nblk] = order[at:at + nblk]
        at += nblk
    return tables


CASES = {
    "len_1": [1],
    "len_bs_minus_1": [BS - 1],
    "len_bs": [BS],
    "len_bs_plus_1": [BS + 1],
    "len_full": [MB * BS],
    "ragged": [1, 37, BS, 129, 5, MB * BS, 64, BS * 8 + 1],
}


@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (4, 4)],
                         ids=["gqa_32_8", "mha"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gathered_attention(case, heads, kv_heads):
    rng = np.random.default_rng(3)
    lengths = np.asarray(CASES[case], np.int32)
    tables = _tables(rng, lengths)
    kp, vp = _pool(rng, kv_heads)
    q = jnp.asarray(rng.normal(size=(len(lengths), heads, HD)),
                    jnp.bfloat16)
    got = pa._paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                    jnp.asarray(lengths),
                                    interpret=_interpret())
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     heads, kv_heads)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("chunk", [1, 3, 8, 64])
def test_kernel_any_chunk_width(chunk):
    """The chunk is a tuning width, not part of the result: partial last
    chunks, a chunk per block and one chunk for the whole row agree."""
    rng = np.random.default_rng(4)
    lengths = np.asarray([70, 1, MB * BS, 33], np.int32)
    tables = _tables(rng, lengths)
    kp, vp = _pool(rng, 2)
    q = jnp.asarray(rng.normal(size=(4, 8, HD)), jnp.bfloat16)
    got = pa._paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                    jnp.asarray(lengths),
                                    blocks_per_chunk=chunk,
                                    interpret=_interpret())
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_shared_prefix_blocks():
    """Two slots whose rows start with the same physical blocks (a radix
    hit) and go on in blocks of their own."""
    rng = np.random.default_rng(5)
    lengths = np.asarray([3 * BS + 5, 3 * BS + 9], np.int32)
    tables = np.full((2, MB), NB, np.int32)
    tables[0, :4] = [40, 7, 19, 3]
    tables[1, :4] = [40, 7, 19, 88]
    kp, vp = _pool(rng, 2)
    q = jnp.asarray(rng.normal(size=(2, 8, HD)), jnp.bfloat16)
    got = pa._paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                    jnp.asarray(lengths),
                                    interpret=_interpret())
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_nothing_outside_a_slots_length_is_read():
    """Vacant slots (all-sentinel rows at pos 0, as the engine leaves
    them) beside live ones, NaN in every block no live slot reads and in
    the tail blocks a live slot owns but has not reached: the result is
    that of the clean pool, and a vacant slot yields zeros."""
    rng = np.random.default_rng(6)
    lengths = np.asarray([1, 50, 1, BS, 1], np.int32)
    live = [False, True, False, True, False]
    tables = _tables(rng, [n if a else 0 for n, a in zip(lengths, live)])
    # a live slot owns its whole budget up front: two more blocks each
    spare = [b for b in range(NB) if b not in set(tables.ravel())]
    tables[1, 4:6] = spare[:2]
    tables[3, 1:3] = spare[2:4]
    read = {int(b) for s in (1, 3)
            for b in tables[s, :-(-int(lengths[s]) // BS)]}
    kp, vp = _pool(rng, 2)
    unread = np.asarray([b not in read for b in range(NB)])
    bad_k = jnp.where(unread[:, None, None, None], jnp.nan, kp)
    bad_v = jnp.where(unread[:, None, None, None], jnp.nan, vp)
    q = jnp.asarray(rng.normal(size=(5, 8, HD)), jnp.bfloat16)
    got = np.asarray(pa._paged_decode_attention(
        q, bad_k, bad_v, jnp.asarray(tables), jnp.asarray(lengths),
        interpret=_interpret()), np.float32)
    want = np.asarray(_gathered(q, kp, vp, jnp.asarray(tables),
                                jnp.asarray(lengths), 8, 2), np.float32)
    assert np.isfinite(got).all()
    for s, a in enumerate(live):
        if a:
            np.testing.assert_allclose(got[s], want[s], atol=2e-2,
                                       rtol=2e-2)
        else:
            assert not got[s].any()


@pytest.mark.parametrize("cols", [2, 4, 5])
def test_verify_window_columns(cols):
    """The speculative verify's K columns through the same kernel:
    column j attends ``lengths + j`` rows (K = 4 fills a bf16 tile at
    four query heads a KV head, 2 and 5 pad it)."""
    rng = np.random.default_rng(7)
    lengths = np.asarray([1, BS - 1, BS, 61, MB * BS - cols + 1], np.int32)
    tables = _tables(rng, lengths + cols - 1)
    kp, vp = _pool(rng, 2)
    q = jnp.asarray(rng.normal(size=(5, cols, 8, HD)), jnp.bfloat16)
    got = pa._paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                    jnp.asarray(lengths),
                                    interpret=_interpret())
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


# --- the step and verify programs through the kernel -------------------------

@pytest.fixture
def wide_decoder(monkeypatch):
    """A two-layer decoder whose heads are 128 wide (the kernel's least),
    with the kernel routed through the interpreter."""
    monkeypatch.setattr(pa, "paged_decode_attention", functools.partial(
        pa._paged_decode_attention, interpret=_interpret()))
    net = llama_tiny(hidden_size=256, intermediate_size=256, num_heads=2,
                     num_kv_heads=1, num_layers=2)
    assert net.config.head_dim == HD
    net.initialize()
    net.cast("bfloat16")
    return LlamaDecoder(net, max_len=MB * BS)


def _step_operands(dec, rng, lengths):
    pos = np.asarray(lengths, np.int32) - 1
    live = pos >= 0
    tables = _tables(rng, [int(n) if a else 0
                           for n, a in zip(lengths, live)])
    kv = dec.cfg.num_kv_heads
    pools = [_pool(rng, kv) for _ in range(dec.cfg.num_layers)]
    ids = jnp.asarray(rng.integers(1, 250, size=len(lengths)), jnp.int32)
    return pools, jnp.asarray(tables), ids, \
        jnp.asarray(np.maximum(pos, 0)), live


def test_step_program_through_kernel_matches_gather(wide_decoder):
    dec = wide_decoder
    rng = np.random.default_rng(8)
    pools, tables, ids, pos, live = _step_operands(
        dec, rng, [40, 0, BS, BS + 1, MB * BS])
    w = dec._weights()
    want, pools_g = dec._step_blocks_impl(w, pools, tables, ids, pos)
    got, pools_k = dec._step_blocks_impl(w, pools, tables, ids, pos,
                                         paged_kernel=True)
    # the scatter of the new row is the same XLA update on both paths:
    # bit for bit in the first layer, whose input no attention has touched
    for a, b in zip(pools_g[0], pools_k[0]):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    for a, b in zip(pools_g[1], pools_k[1]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2, rtol=3e-2)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=3e-2, rtol=3e-2)
    assert (got[live].argmax(-1) == want[live].argmax(-1)).all()


def test_verify_program_through_kernel_matches_gather(wide_decoder):
    dec = wide_decoder
    rng = np.random.default_rng(9)
    kk = 4
    lengths = [40, 0, BS - 1, MB * BS - kk + 1]
    pools, tables, _ids, pos0, live = _step_operands(
        dec, rng, [n + kk - 1 if n else 0 for n in lengths])
    pos0 = jnp.asarray(np.maximum(np.asarray(lengths) - 1, 0), jnp.int32)
    toks = jnp.asarray(rng.integers(1, 250, size=(len(lengths), kk)),
                       jnp.int32)
    w = dec._weights()
    want, _ = dec._verify_blocks_impl(w, pools, tables, toks, pos0)
    got, _ = dec._verify_blocks_impl(w, pools, tables, toks, pos0,
                                     paged_kernel=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[live], want[live], atol=3e-2, rtol=3e-2)


# --- which path an engine takes ----------------------------------------------

@pytest.mark.parametrize("platform,mesh,head_dim,block,dtype,want", [
    ("tpu", None, 128, 16, "bfloat16", True),
    ("tpu", None, 256, 32, "bfloat16", True),
    ("tpu", None, 128, 8, "float32", True),
    ("cpu", None, 128, 16, "bfloat16", False),      # the tier-1 tests
    ("tpu", "a mesh", 128, 16, "bfloat16", False),  # tp-sharded pool
    ("tpu", None, 16, 16, "bfloat16", False),       # llama_tiny's heads
    ("tpu", None, 64, 16, "bfloat16", False),
    ("tpu", None, 128, 8, "bfloat16", False),       # half a bf16 tile
    ("tpu", None, 128, 16, "int8", False),
])
def test_applicable(platform, mesh, head_dim, block, dtype, want):
    assert pa.applicable(platform, mesh, head_dim, block,
                         jnp.dtype(dtype)) is want


def test_engines_here_take_the_gather_path():
    """``llama_tiny`` on the CPU, alone and on a mesh: the engine says
    ``gather``, ``server.stats()`` and the first ``decode.tick`` record
    repeat it, and ticks carry ``kv_tokens``."""
    from jax.sharding import Mesh

    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerConfig
    from mxnet_tpu.serving.generative import LlamaServingEngine
    from mxnet_tpu.telemetry import tracing

    net = llama_tiny()
    net.initialize()
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    eng = LlamaServingEngine(net, max_len=64, num_slots=2, kv_mode="paged",
                             mesh=mesh)
    assert eng.decode_attention == "gather"
    # the mesh alone decides it, whatever the platform and the shapes
    assert pa.applicable("tpu", mesh, 128, 16, jnp.bfloat16) is False
    assert LlamaServingEngine(net, max_len=64, num_slots=2,
                              kv_mode="slots").decode_attention == "gather"

    since = time.perf_counter()
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2)
    with serving.GenerativeServer(net, cfg) as srv:
        prompt = np.arange(1, 8)
        srv.generate(prompt, max_new_tokens=4)
        stats = srv.stats()
    assert srv.engine.decode_attention == "gather"
    assert stats["decode_attention"] == "gather"
    ticks = tracing.lane_log("decode.tick", since=since)
    assert ticks[0]["decode_attention"] == "gather"
    assert all("decode_attention" not in r for r in ticks[1:])
    # one request: tick k wrote row len(prompt) + k - 1 and attended it
    assert [r["kv_tokens"] for r in ticks] == \
        [len(prompt) + k for k in range(1, len(ticks) + 1)]


# --- the kernel compiles for the chip ----------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots,max_blocks,num_blocks,cols,dtype", [
    (64, 64, 4096, 1, "bfloat16"),      # mistral7b.chat_decode_sat
    (16, 256, 2048, 1, "bfloat16"),     # mistral7b.doc_prefill
    (64, 64, 4096, 4, "bfloat16"),      # a verify window of k = 3
    (16, 256, 2048, 1, "float32"),      # a net served as it was trained
], ids=["chat_64x1024", "doc_16x4096", "verify_k3", "float32"])
def test_kernel_compiles_for_v5e(one_chip, slots, max_blocks, num_blocks,
                                 cols, dtype):
    """Mosaic takes the kernel at the benchmark cells' shapes (32 / 8
    heads of 128, blocks of 16) and the compiled program holds no array
    of a gathered view's size."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = (slots, 32, 128) if cols == 1 else (slots, cols, 32, 128)
    dtype = jnp.dtype(dtype)
    pool = sds((num_blocks, 8, 16, 128), dtype)
    # as the chip runs it: 32-bit (Mosaic takes no 64-bit index, and
    # tests/conftest.py turns x64 on), and outside the persistent cache,
    # which a compile for a described chip can write but never read back
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            text = jax.jit(pa.paged_decode_attention).lower(
                sds(q, dtype), pool, pool,
                sds((slots, max_blocks), jnp.int32),
                sds((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    for kv in (8, 32):
        assert f"[{slots},{kv},{max_blocks * 16},128]" not in text

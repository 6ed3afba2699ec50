"""Paged decode attention (``mxnet_tpu/ops/paged_attention.py``): the
Pallas kernel under the TPU interpreter against
``ops.attention.masked_attention`` on the gathered view, the step and verify programs through it, which
path an engine picks, and a compile of the kernel for the v5e at the
benchmark cells' shapes (no chip: the described topology).  Heads of 64
run the same tests on a PACKED pool (two KV heads to a 128-lane row)
against ``masked_attention`` on the gathered unpacked one."""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models.llama import LlamaDecoder, llama_tiny
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.ops.attention import masked_attention

BS, MB, NB = 16, 12, 96

#: head widths the bare kernel is run at: 128 (a head a lane row) and 64
#: (two KV heads a row)
head_dims = pytest.mark.parametrize("hd", [128, 64], ids=["hd128", "hd64"])


def _interpret():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()


def _gathered(q, kp, vp, tables, lengths, heads, kv_heads):
    """The present step's attention: the clamped gather of every slot's
    whole view, then ``masked_attention`` under the per-slot (and, for a verify
    window, per-column) length mask.  q (S, H, hd) or (S, K, H, hd);
    the pools UNPACKED, (NB, Hkv, BS, hd)."""
    s, hd = q.shape[0], q.shape[-1]
    q4 = q[:, None] if q.ndim == 3 else q           # (S, K, H, hd)
    cols = q4.shape[1]
    gat = jnp.minimum(tables, kp.shape[0] - 1)
    kc = kp[gat].transpose(0, 2, 1, 3, 4).reshape(s, kv_heads, -1, hd)
    vc = vp[gat].transpose(0, 2, 1, 3, 4).reshape(s, kv_heads, -1, hd)
    bound = lengths[:, None] + jnp.arange(cols)[None, :]        # (S, K)
    mask = (jnp.arange(kc.shape[2])[None, None, :]
            < bound[:, :, None])[:, None]                       # (S,1,K,T)
    out = masked_attention(q4.transpose(0, 2, 1, 3), kc, vc,
                           mask)                                # (S,H,K,hd)
    return out.transpose(0, 2, 1, 3).reshape(q.shape)


def _pool(rng, kv_heads, hd=128):
    """An unpacked (K, V) pool pair, one head a row."""
    shape = (NB, kv_heads, BS, hd)
    return (jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16))


def _stored(pools, hd):
    """The pools as an engine under the kernel stores them."""
    return tuple(pa.pack_rows(p, max(1, 128 // hd)) for p in pools)


def _kernel(q, kp, vp, tables, lengths, **kw):
    """The bare kernel under the interpreter, on the stored form of the
    unpacked pools ``kp`` / ``vp``."""
    kp, vp = _stored((kp, vp), q.shape[-1])
    return pa._paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                      jnp.asarray(lengths),
                                      interpret=_interpret(), **kw)


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


@head_dims
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (4, 4)],
                         ids=["gqa_32_8", "mha"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gathered_attention(case, heads, kv_heads, hd):
    rng = np.random.default_rng(3)
    lengths = np.asarray(CASES[case], np.int32)
    tables = _tables(rng, lengths)
    kp, vp = _pool(rng, kv_heads, hd)
    q = jnp.asarray(rng.normal(size=(len(lengths), heads, hd)),
                    jnp.bfloat16)
    got = _kernel(q, kp, vp, tables, lengths)
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     heads, kv_heads)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@head_dims
@pytest.mark.parametrize("chunk", [1, 3, 8, 64])
def test_kernel_any_chunk_width(chunk, hd):
    """The chunk is a tuning width, not part of the result: partial last
    chunks, a chunk per block and one chunk for the whole row agree."""
    rng = np.random.default_rng(4)
    lengths = np.asarray([70, 1, MB * BS, 33], np.int32)
    tables = _tables(rng, lengths)
    kp, vp = _pool(rng, 2, hd)
    q = jnp.asarray(rng.normal(size=(4, 8, hd)), jnp.bfloat16)
    got = _kernel(q, kp, vp, tables, lengths, blocks_per_chunk=chunk)
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@head_dims
def test_shared_prefix_blocks(hd):
    """Two slots whose rows start with the same physical blocks (a radix
    hit) and go on in blocks of their own."""
    rng = np.random.default_rng(5)
    lengths = np.asarray([3 * BS + 5, 3 * BS + 9], np.int32)
    tables = np.full((2, MB), NB, np.int32)
    tables[0, :4] = [40, 7, 19, 3]
    tables[1, :4] = [40, 7, 19, 88]
    kp, vp = _pool(rng, 2, hd)
    q = jnp.asarray(rng.normal(size=(2, 8, hd)), jnp.bfloat16)
    got = _kernel(q, kp, vp, tables, lengths)
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@head_dims
def test_nothing_outside_a_slots_length_is_read(hd):
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
    kp, vp = _pool(rng, 2, hd)
    unread = np.asarray([b not in read for b in range(NB)])
    bad_k = jnp.where(unread[:, None, None, None], jnp.nan, kp)
    bad_v = jnp.where(unread[:, None, None, None], jnp.nan, vp)
    q = jnp.asarray(rng.normal(size=(5, 8, hd)), jnp.bfloat16)
    got = np.asarray(_kernel(q, bad_k, bad_v, tables, lengths), np.float32)
    want = np.asarray(_gathered(q, kp, vp, jnp.asarray(tables),
                                jnp.asarray(lengths), 8, 2), np.float32)
    assert np.isfinite(got).all()
    for s, a in enumerate(live):
        if a:
            np.testing.assert_allclose(got[s], want[s], atol=2e-2,
                                       rtol=2e-2)
        else:
            assert not got[s].any()


@head_dims
@pytest.mark.parametrize("cols", [2, 4, 5])
def test_verify_window_columns(cols, hd):
    """The speculative verify's K columns through the same kernel:
    column j attends ``lengths + j`` rows (K = 4 fills a bf16 tile at
    four query heads a KV head, 2 and 5 pad it)."""
    rng = np.random.default_rng(7)
    lengths = np.asarray([1, BS - 1, BS, 61, MB * BS - cols + 1], np.int32)
    tables = _tables(rng, lengths + cols - 1)
    kp, vp = _pool(rng, 2, hd)
    q = jnp.asarray(rng.normal(size=(5, cols, 8, hd)), jnp.bfloat16)
    got = _kernel(q, kp, vp, tables, lengths)
    want = _gathered(q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
                     8, 2)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


# --- the step and verify programs through the kernel -------------------------

@pytest.fixture(params=[(128, 2, 1), (64, 4, 2)], ids=["hd128", "hd64"])
def wide_decoder(request, monkeypatch):
    """A two-layer decoder whose heads are 128 wide (a head a lane row)
    or 64 (its two KV heads packed to one), with the kernel routed
    through the interpreter."""
    hd, heads, kv_heads = request.param
    monkeypatch.setattr(pa, "paged_decode_attention", functools.partial(
        pa._paged_decode_attention, interpret=_interpret()))
    net = llama_tiny(hidden_size=256, intermediate_size=256,
                     num_heads=heads, num_kv_heads=kv_heads, num_layers=2)
    assert net.config.head_dim == hd
    net.initialize()
    net.cast("bfloat16")
    return LlamaDecoder(net, max_len=MB * BS)


def _step_operands(dec, rng, lengths):
    pos = np.asarray(lengths, np.int32) - 1
    live = pos >= 0
    tables = _tables(rng, [int(n) if a else 0
                           for n, a in zip(lengths, live)])
    cfg = dec.cfg
    pools = [_pool(rng, cfg.num_kv_heads, cfg.head_dim)
             for _ in range(cfg.num_layers)]
    ids = jnp.asarray(rng.integers(1, 250, size=len(lengths)), jnp.int32)
    return pools, jnp.asarray(tables), ids, \
        jnp.asarray(np.maximum(pos, 0)), live


def test_step_program_through_kernel_matches_gather(wide_decoder):
    """The kernel path on the pool as its engine stores it (packed at
    heads of 64) against the gather path on the unpacked one: logits,
    and the written pools read back through ``unpack_rows``."""
    dec = wide_decoder
    hd = dec.cfg.head_dim
    rng = np.random.default_rng(8)
    pools, tables, ids, pos, live = _step_operands(
        dec, rng, [40, 0, BS, BS + 1, MB * BS])
    w = dec._weights()
    want, pools_g = dec._step_blocks_impl(w, pools, tables, ids, pos)
    got, pools_k = dec._step_blocks_impl(
        w, [_stored(pair, hd) for pair in pools], tables, ids, pos,
        paged_kernel=True)
    pools_k = [tuple(pa.unpack_rows(p, 128 // hd) for p in pair)
               for pair in pools_k]
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


def test_gather_path_reads_a_packed_pool(wide_decoder):
    """The step's and the verify's gather branches on a packed pool (no
    engine builds that pair; the branch must not rot) equal themselves
    on the unpacked one, bit for bit."""
    dec = wide_decoder
    hd = dec.cfg.head_dim
    rng = np.random.default_rng(10)
    pools, tables, ids, pos, live = _step_operands(
        dec, rng, [40, 0, BS, BS + 1, MB * BS - 3])
    packed = [_stored(pair, hd) for pair in pools]
    w = dec._weights()
    toks = jnp.asarray(rng.integers(1, 250, size=(len(live), 3)), jnp.int32)
    for impl, arg in ((dec._step_blocks_impl, ids),
                      (dec._verify_blocks_impl, toks)):
        want, pools_u = impl(w, pools, tables, arg, pos)
        got, pools_p = impl(w, packed, tables, arg, pos)
        assert np.array_equal(np.asarray(got, np.float32)[live],
                              np.asarray(want, np.float32)[live])
        for pu, pp in zip(pools_u, pools_p):
            for a, b in zip(pu, pp):
                assert np.array_equal(
                    np.asarray(a, np.float32),
                    np.asarray(pa.unpack_rows(b, 128 // hd), np.float32))


def test_verify_program_through_kernel_matches_gather(wide_decoder):
    dec = wide_decoder
    hd = dec.cfg.head_dim
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
    got, _ = dec._verify_blocks_impl(
        w, [_stored(pair, hd) for pair in pools], tables, toks, pos0,
        paged_kernel=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[live], want[live], atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("pack", [1, 2, 4])
def test_pack_rows_round_trip(pack):
    """Head ``r * pack + p`` lands in lanes ``[p * hd, (p + 1) * hd)`` of
    head row ``r``, any leading axes, and ``unpack_rows`` undoes it."""
    hd = 128 // pack
    a = jnp.arange(3 * 8 * 5 * hd, dtype=jnp.float32).reshape(3, 8, 5, hd)
    p = pa.pack_rows(a, pack)
    assert p.shape == (3, 8 // pack, 5, pack * hd)
    for head in range(8):
        r, at = divmod(head, pack)
        assert np.array_equal(p[:, r, :, at * hd:(at + 1) * hd], a[:, head])
    assert np.array_equal(pa.unpack_rows(p, pack), a)
    assert np.array_equal(pa.unpack_rows(pa.pack_rows(a[None], pack), pack),
                          a[None])


@pytest.mark.parametrize("pack", [1, 2])
def test_layout_round_trip_scatter_write_gather(pack):
    """The pool's format, end to end through its one owner: rows that
    the prefill scatter wrote and a row the step write added read back
    through ``gathered_view`` as the logical ``(S, Hkv, T, hd)`` rows,
    whatever ``pack``; writes at the sentinel id (a short prompt's
    unallocated chunk, a vacant slot, a slot whose next block is not
    there, a verify column past ``max_len``) drop."""
    hd, hkv, nb, mb = 128 // pack, 4, 8, 4
    rng = np.random.default_rng(20 + pack)
    pool = jnp.zeros(pa.pool_shape(nb, hkv, hd, BS, pack), jnp.float32)
    assert pool.shape == (nb, hkv // pack, BS, 128)
    rows = jnp.asarray(rng.normal(size=(2, hkv, 40, hd)), jnp.float32)
    # prompt 0 owns three blocks, prompt 1 only its first chunk's
    flat = jnp.asarray([5, 2, 7, 1, nb, nb], jnp.int32)
    pool = pa.scatter_rows(pool, rows, flat)
    tables = jnp.asarray([[5, 2, 7, nb], [1, nb, nb, nb], [nb] * 4],
                         jnp.int32)
    pos = jnp.asarray([40, BS, 0], jnp.int32)
    new = jnp.asarray(rng.normal(size=(3, hkv, 1, hd)), jnp.float32)
    win = pa.window(pool, tables, pos, mb * BS, False)
    assert np.array_equal(win.live, [True, True, False])
    written = pa.write_rows(pool, win, new)
    view = np.asarray(pa.gathered_view(written, win.gat, pack))
    assert view.shape == (3, hkv, mb * BS, hd)
    assert np.array_equal(view[0, :, :40], rows[0])
    assert np.array_equal(view[0, :, 40], new[0, :, 0])
    assert np.array_equal(view[1, :, :BS], rows[1, :, :BS])
    # one row changed in the whole pool: slots 1 and 2 wrote nothing
    changed = np.argwhere((np.asarray(written) != np.asarray(pool))
                          .any(axis=(1, 3)))
    assert changed.tolist() == [[7, 40 % BS]]
    # a window of two columns a slot: the second of slot 0 lies past
    # max_len and drops, slot 1's block is not there
    cols = jnp.asarray(rng.normal(size=(3, hkv, 2, hd)), jnp.float32)
    pw = jnp.asarray([[47, 48], [BS, BS + 1], [0, 1]], jnp.int32)
    win2 = pa.window(pool, tables, pw, 48, False)
    wide = pa.write_rows(pool, win2, cols)
    view2 = np.asarray(pa.gathered_view(wide, win2.gat, pack))
    assert np.array_equal(view2[0, :, 47], cols[0, :, 0])
    changed = np.argwhere((np.asarray(wide) != np.asarray(pool))
                          .any(axis=(1, 3)))
    assert changed.tolist() == [[7, 47 % BS]]


def _tiny_net(name):
    if name == "llama_tiny":
        net = llama_tiny()
    else:
        from mxnet_tpu.models.lfm2 import lfm2_moe_tiny

        net = lfm2_moe_tiny()
    net.initialize()
    return net


@pytest.mark.parametrize("name", ["llama_tiny", "lfm2_moe_tiny"])
def test_prefill_then_paged_steps_equal_the_gluon_forward(name):
    """The programs both families inherit from ``PagedDecoder``: prefill,
    the hand-over into blocks, then one paged step a token give, at
    every position, the logits of the net's own ``hybrid_forward`` over
    the whole sequence."""
    from mxnet_tpu import nd
    from mxnet_tpu.models.decoder import PagedDecoder
    from mxnet_tpu.serving.generative import LlamaServingEngine

    net = _tiny_net(name)
    seq = np.random.default_rng(5).integers(1, 250, size=19)
    want = net(nd.array(seq[None], dtype="int32")).asnumpy()[0]
    eng = LlamaServingEngine(net, max_len=32, num_slots=2, block_size=4)
    dec, w, t0, slot = eng._dec, eng._w, 6, 1
    for impl in ("_step_blocks_impl", "_verify_blocks_impl",
                 "_prefill_rows_impl", "_prefill_suffix_impl"):
        assert getattr(type(dec), impl) is getattr(PagedDecoder, impl)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :t0] = seq[:t0]
    rows, lg = dec._prefill_rows_impl(w, jnp.asarray(ids),
                                      jnp.asarray([t0]))[:2]
    got = [np.asarray(lg)[0]]
    eng.commit_rows(rows, np.asarray([slot]), [list(range(8))],
                    np.asarray([t0]), np.asarray([seq[t0 - 1]]))
    for t in range(t0, len(seq)):
        ids_t, pos = np.zeros(2, np.int32), np.zeros(2, np.int32)
        ids_t[slot], pos[slot] = seq[t], t
        lg, eng._pool = dec._step_blocks_impl(
            w, eng._pool, jnp.asarray(eng._tables), jnp.asarray(ids_t),
            jnp.asarray(pos))[:2]
        got.append(np.asarray(lg)[slot])
    got, want = np.stack(got), want[t0 - 1:]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


def test_layering_models_and_ops_below_serving_and_one_owner_of_the_pool():
    """``serving`` -> ``models`` -> ``ops``, one way: no module under
    ``mxnet_tpu/models`` or ``mxnet_tpu/ops`` imports
    ``mxnet_tpu.serving``.  And the pool's storage format has one owner:
    an indexed update that drops out-of-bounds ids
    (``x.at[...].set(..., mode="drop")``, how a pool is written around
    its sentinel) appears in ``ops/paged_attention.py`` and nowhere
    else in the package, but for the engine's write of a state layer's
    rows by slot."""
    import ast
    import os

    import mxnet_tpu

    root = os.path.dirname(mxnet_tpu.__file__)
    reaching_up, drop_writes = [], []
    for folder, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path) as fh:
                tree = ast.parse(fh.read(), path)
            depth = rel.count("/")
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.ImportFrom):
                    base = node.module or ""
                    # ``from ..serving`` one folder down is the package's
                    if node.level and node.level - 1 == depth:
                        mods = ["mxnet_tpu." + base] + [
                            "mxnet_tpu." + a.name for a in node.names
                            if not base]
                    elif not node.level:
                        mods = [base] + [base + "." + a.name
                                         for a in node.names]
                elif isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                if rel.startswith(("models/", "ops/")) and any(
                        m == "mxnet_tpu.serving"
                        or m.startswith("mxnet_tpu.serving.") for m in mods):
                    reaching_up.append((rel, node.lineno))
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "set"
                        and isinstance(node.func.value, ast.Subscript)
                        and isinstance(node.func.value.value, ast.Attribute)
                        and node.func.value.value.attr == "at"
                        and any(k.arg == "mode"
                                and getattr(k.value, "value", None) == "drop"
                                for k in node.keywords)):
                    drop_writes.append(
                        (rel, ast.unparse(node.func.value.slice)))
    assert reaching_up == []
    assert {w for w in drop_writes
            if w[0] != "ops/paged_attention.py"} == \
        {("serving/generative.py", "slots")}
    assert len([w for w in drop_writes
                if w[0] == "ops/paged_attention.py"]) == 2


# --- which path an engine takes ----------------------------------------------

@pytest.mark.parametrize("platform,mesh,head_dim,kv_heads,block,dtype,want", [
    ("tpu", None, 128, 8, 16, "bfloat16", 1),
    ("tpu", None, 256, 8, 32, "bfloat16", 1),
    ("tpu", None, 128, 8, 8, "float32", 1),
    ("cpu", None, 128, 8, 16, "bfloat16", 0),       # the tier-1 tests
    ("tpu", "a mesh", 128, 8, 16, "bfloat16", 0),   # tp-sharded pool
    ("tpu", None, 16, 2, 16, "bfloat16", 0),        # llama_tiny's heads
    ("tpu", None, 64, 8, 16, "bfloat16", 2),        # two heads a lane row
    ("tpu", None, 64, 2, 16, "bfloat16", 2),
    ("tpu", None, 64, 3, 16, "bfloat16", 0),        # an odd head left over
    ("tpu", None, 64, 1, 16, "bfloat16", 0),
    ("cpu", None, 64, 8, 16, "bfloat16", 0),
    ("tpu", "a mesh", 64, 8, 16, "bfloat16", 0),
    ("tpu", None, 32, 8, 16, "bfloat16", 0),        # four a row: not built
    ("tpu", None, 96, 8, 16, "bfloat16", 0),        # fills no lane row
    ("tpu", None, 256, 3, 16, "bfloat16", 1),       # no pairing needed
    ("tpu", None, 128, 8, 8, "bfloat16", 0),        # half a bf16 tile
    ("tpu", None, 128, 8, 16, "int8", 0),
])
def test_applicable(platform, mesh, head_dim, kv_heads, block, dtype, want):
    """0: the gather path; else the KV heads a stored lane row holds."""
    got = pa.applicable(platform, mesh, head_dim, kv_heads, block,
                        jnp.dtype(dtype))
    assert got == want and isinstance(got, int)


@pytest.fixture
def as_on_a_chip(monkeypatch):
    """Steer an engine built here as a TPU would: ``applicable`` sees
    the platform ``tpu`` whatever the weights live on, and the kernel
    runs through the interpreter."""
    real = pa.applicable
    monkeypatch.setattr(pa, "applicable",
                        lambda platform, *rest: real("tpu", *rest))
    monkeypatch.setattr(pa, "paged_decode_attention", functools.partial(
        pa._paged_decode_attention, interpret=_interpret()))


def _engine_hd64(**kw):
    from mxnet_tpu.serving.generative import LlamaServingEngine

    net = llama_tiny(hidden_size=256, intermediate_size=256, num_heads=4,
                     num_kv_heads=2, num_layers=2)
    net.initialize()
    net.cast("bfloat16")
    return LlamaServingEngine(net, max_len=64, num_slots=2, block_size=16,
                              **kw)


def test_engine_at_heads_of_64_here_stores_one_head_a_row():
    eng = _engine_hd64()
    assert (eng.decode_attention, eng.kv_pack) == ("gather", 1)
    assert eng._pool[0][0].shape == (8, 2, 16, 64)


def test_packed_engine_scatter_step_gather_round_trip(as_on_a_chip):
    """An engine whose ``applicable`` says two heads a row: prefilled rows
    go in through the scatter, the step writes one more row, and
    ``gather_prefix`` (the radix cache's read) returns, unpacked, the
    rows that went in; the step through the kernel on that pool agrees
    with the gather path on the unpacked one."""
    eng = _engine_hd64()
    assert (eng.decode_attention, eng.kv_pack) == ("paged_kernel", 2)
    assert eng._pool[0][0].shape == (8, 1, 16, 128)
    t0 = 21
    ids = np.zeros((1, 32), np.int32)
    ids[0, :t0] = np.random.default_rng(11).integers(1, 250, size=t0)
    first, rows = eng.prefill_rows(ids, np.asarray([t0], np.int32))
    blocks = [5, 2, 7, 0]                       # slot 1, shuffled
    eng.commit_rows(rows, np.asarray([1]), [blocks], np.asarray([t0]),
                    np.asarray(first))
    dec, w = eng._dec, eng._w
    tables = jnp.asarray(eng._tables)
    ids_t = jnp.asarray([0, 9], jnp.int32)
    pos = jnp.asarray([0, t0], jnp.int32)
    want, pool_g = dec._step_blocks_impl(
        w, [tuple(pa.unpack_rows(p, 2) for p in pair)
            for pair in eng._pool], tables, ids_t, pos)
    got, eng._pool = dec._step_blocks_impl(w, eng._pool, tables, ids_t, pos,
                                           paged_kernel=True)
    np.testing.assert_allclose(np.asarray(got[1], np.float32),
                               np.asarray(want[1], np.float32),
                               atol=3e-2, rtol=3e-2)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    views = eng.gather_prefix(np.asarray([blocks[:2]], np.int32))
    for l, (view, row, pair_g) in enumerate(zip(views, rows, pool_g)):
        for g, r, u in zip(view, row, pair_g):
            assert g.shape == (1, 2, 32, 64)
            g, stepped = f32(g)[0], f32(u)[blocks[1], :, t0 - 16]
            # the prefilled rows, then the step's own at position t0
            assert np.array_equal(g[:, :t0], f32(r)[0, :, :t0])
            assert g[:, t0].any()
            if l == 0:      # no attention has touched the first layer's
                assert np.array_equal(g[:, t0], stepped)
            else:
                np.testing.assert_allclose(g[:, t0], stepped, atol=3e-2,
                                           rtol=3e-2)


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
    eng = LlamaServingEngine(net, max_len=64, num_slots=2, mesh=mesh)
    assert eng.decode_attention == "gather" and eng.kv_pack == 1
    # the mesh alone decides it, whatever the platform and the shapes
    assert pa.applicable("tpu", mesh, 128, 8, 16, jnp.bfloat16) == 0

    since = time.perf_counter()
    cfg = ServerConfig(max_batch=2, max_length=64, min_length=8,
                       num_slots=2)
    with serving.GenerativeServer(net, cfg) as srv:
        prompt = np.arange(1, 8)
        srv.generate(prompt, max_new_tokens=4)
        stats = srv.stats()
    assert srv.engine.decode_attention == "gather"
    assert stats["decode_attention"] == "gather" and stats["kv_pack"] == 1
    assert stats["prefill_attention"] == "dense"
    ticks = tracing.lane_log("decode.tick", since=since)
    assert ticks[0]["decode_attention"] == "gather"
    assert ticks[0]["kv_pack"] == 1
    assert all("decode_attention" not in r and "kv_pack" not in r
               for r in ticks[1:])
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


@pytest.mark.parametrize(
    "slots,max_blocks,num_blocks,cols,dtype,heads,kv_heads,hd", [
        (64, 64, 4096, 1, "bfloat16", 32, 8, 128),  # mistral7b.chat_decode_sat
        (16, 256, 2048, 1, "bfloat16", 32, 8, 128),     # mistral7b.doc_prefill
        (64, 64, 4096, 4, "bfloat16", 32, 8, 128),  # a verify window of k = 3
        (16, 256, 2048, 1, "float32", 32, 8, 128),  # a net served as trained
        (128, 64, 8192, 1, "bfloat16", 32, 8, 64),  # lfm2_24b.chat_decode_sat
        (128, 64, 8192, 4, "bfloat16", 32, 8, 64),  # heads of 64, verify k = 3
        (128, 64, 8192, -4, "bfloat16", 32, 4, 128),  # sdar_30b: a BLOCK of 4
        # ouro_2_6b: ONE query head a KV head, a pool of 4 passes x 320 blocks
        (8, 96, 1280, 1, "bfloat16", 16, 16, 128),
    ], ids=["chat_64x1024", "doc_16x4096", "verify_k3", "float32",
            "lfm2_128x1024_hd64", "verify_k3_hd64", "sdar_block4",
            "ouro_8x1536_group1"])
def test_kernel_compiles_for_v5e(one_chip, slots, max_blocks, num_blocks,
                                 cols, dtype, heads, kv_heads, hd):
    """Mosaic takes the kernel at the benchmark cells' shapes (32 query
    and 8 KV heads of 128, or of 64 stored two to a lane row:
    ``(8192, 4, 16, 128)``; blocks of 16) and the compiled program holds
    no array of a gathered view's size and no copy of the pool."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # negative columns: a block decoder's window, every column seeing
    # all of them (8 query heads a KV head x 4 columns: 32 score rows)
    block, cols = cols < 0, abs(cols)
    q = (slots, heads, hd) if cols == 1 else (slots, cols, heads, hd)
    dtype = jnp.dtype(dtype)
    pack = pa.applicable("tpu", None, hd, kv_heads, 16, dtype)
    stored = (num_blocks, kv_heads // pack, 16, pack * hd)
    assert stored[-1] % 128 == 0
    pool = sds(stored, dtype)
    # as the chip runs it: 32-bit (Mosaic takes no 64-bit index, and
    # tests/conftest.py turns x64 on), and outside the persistent cache,
    # which a compile for a described chip can write but never read back
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            text = jax.jit(functools.partial(
                pa.paged_decode_attention, block=block)).lower(
                sds(q, dtype), pool, pool,
                sds((slots, max_blocks), jnp.int32),
                sds((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    for kv in (kv_heads // pack, kv_heads, heads):
        assert f"[{slots},{kv},{max_blocks * 16},{hd}]" not in text
        assert f"[{slots},{kv},{max_blocks * 16},128]" not in text
    # the pool goes to the kernel as it came in: nothing re-lays it
    shape = ",".join(map(str, stored))
    assert not [ln for ln in text.splitlines()
                if f"[{shape}]" in ln.split("=")[0]
                and (" copy(" in ln or " convert(" in ln
                     or " transpose(" in ln)]


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "dense"])
def test_prefill_program_compiles_for_v5e_without_scores(one_chip, flash):
    """Mistral-7B's prefill program at the 4,096 bucket (its widths: 32
    query / 8 KV heads of 128, hidden 4,096, feed-forward 14,336; two
    layers: every layer's arrays have the cell's shapes, and a
    program's temporaries are one layer's) through the flash forward
    kernel: one Mosaic call a layer, no ``(1, 32, 4096, 4096)`` score
    tensor in any dtype, K and V never broadcast over their group, and
    temporaries under 1 GB — the dense path's 5 GB, which the second
    case pins so that the first cannot pass by looking at the wrong
    text."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    lp, heads, kv_heads, hd, hidden, ffn, vocab = \
        4096, 32, 8, 128, 4096, 14336, 32768
    net = LlamaForCausalLM(LlamaConfig(
        hidden_size=hidden, intermediate_size=ffn, num_layers=2,
        num_heads=heads, num_kv_heads=kv_heads, vocab_size=vocab,
        max_seq_len=lp, rope_theta=1e6, tie_embeddings=False))
    dec = LlamaDecoder(net, max_len=lp)     # never initialized: shapes only

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = dict(ln_in=sds(hidden), q=sds(heads * hd, hidden),
                 k=sds(kv_heads * hd, hidden), v=sds(kv_heads * hd, hidden),
                 o=sds(hidden, heads * hd), ln_post=sds(hidden),
                 gate=sds(ffn, hidden), up=sds(ffn, hidden),
                 down=sds(hidden, ffn))
    w = dict(layers=[layer, layer], emb=sds(vocab, hidden),
             norm=sds(hidden), head=sds(vocab, hidden))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            compiled = jax.jit(functools.partial(
                dec._prefill_rows_impl, flash=flash)).lower(
                    w, sds(1, lp, dtype=jnp.int32),
                    sds(1, dtype=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    text = compiled.as_text()
    temps = compiled.memory_analysis().temp_size_in_bytes
    # the compiler drops the unit batch axis
    scores = re.findall(rf"\w+\[(?:1,)?{heads},{lp},{lp}\]", text)
    # jnp.repeat of K or V: a broadcast over the group, then a reshape
    repeats = re.findall(
        rf"= \w+\[(?:1,)?{kv_heads},{heads // kv_heads},{lp},{hd}\]\S* "
        r"broadcast\(", text)
    kernels = text.count('custom_call_target="tpu_custom_call"')
    if flash:
        assert kernels == 2 and not scores and not repeats
        assert "prefill_flash_attention" in text
        assert temps < 1e9, temps
    else:
        assert kernels == 0 and f"f32[{heads},{lp},{lp}]" in scores
        assert repeats and temps > 4e9, temps


@pytest.mark.parametrize("program", ["step", "prefill_512"])
def test_looped_programs_compile_for_v5e_with_the_stack_held_once(one_chip,
                                                                  program):
    """Ouro-2.6B's step and 512 prefill bucket at the published widths and
    ``ouro_2_6b.reason_decode_sat``'s shapes (8 slots x 1,536, 4 passes x 320
    blocks a layer; three layers: every layer's arrays have the cell's
    shapes): ONE loop over the four passes, a kernel a LAYER and not a layer a
    pass, the pools aliased through the loop with no pool-sized copy, and
    temporaries far under a pool's 80 MiB."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.models import ouro

    layers, slots, max_len, blocks = 3, 8, 1536, 320
    conf = ouro.OuroConfig(num_layers=layers, max_seq_len=max_len)
    dec = ouro.OuroDecoder(ouro.OuroForCausalLM(conf), max_len)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = {n: sds(*shape)
             for n, shape in ouro._layer_param_shapes(conf).items()}
    w = dict(layers=[layer] * layers, emb=sds(49152, 2048), norm=sds(2048),
             head=sds(49152, 2048))
    stored = (4 * blocks, 16, 16, 128)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            if program == "step":
                compiled = jax.jit(
                    functools.partial(dec._step_blocks_impl, paged_kernel=True),
                    donate_argnums=1).lower(
                        w, [(sds(*stored), sds(*stored))] * layers,
                        sds(slots, max_len // 16, dtype=jnp.int32),
                        sds(slots, dtype=jnp.int32),
                        sds(slots, dtype=jnp.int32)).compile()
            else:
                compiled = jax.jit(functools.partial(
                    dec._prefill_rows_impl, flash=True)).lower(
                        w, sds(1, 512, dtype=jnp.int32),
                        sds(1, dtype=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == layers
    assert text.count(" while(") == 1
    if program == "step":
        assert "paged_decode_attention" in text
        pool_bytes = 2 * int(np.prod(stored))
        assert mem.alias_size_in_bytes == 2 * layers * pool_bytes
        assert mem.temp_size_in_bytes < pool_bytes // 4
        shape = ",".join(map(str, stored))
        assert not re.search(rf"= \w+\[{shape}\]\S* (copy|transpose)\(", text)
    else:
        assert "prefill_flash_attention" in text
        # what goes out: a pass's K and V rows a layer, four passes stacked
        assert mem.output_size_in_bytes >= 4 * layers * 2 * 16 * 512 * 128 * 2
        assert mem.temp_size_in_bytes < 2 ** 28


@pytest.mark.parametrize("shape,dtype", [
    ((128, 12, 128, 64), "bfloat16"),   # bert_base.pretrain_s128
    ((128, 12, 128, 64), "float32"),    # the same without autocast
    ((16, 8, 512, 128), "bfloat16"),    # the longest one-tile sequence
    ((2, 8, 1024, 64), "bfloat16"),     # past one tile: a row a step
    ((1, 4, 4096, 256), "bfloat16"),    # heads of 256: dq and dkv at 512
    ((1, 4, 2048, 128), "float32"),     # float32 blocks at tiles of 1,024
    ((1, 4, 4096, 256), "float32"),     # the forward's second-best tiles
], ids=["bert_s128_bf16", "bert_s128_f32", "t512_hd128", "t1024_hd64",
        "t4096_hd256", "t2048_hd128_f32", "t4096_hd256_f32"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_training_flash_kernels_compile_for_v5e(one_chip, shape, dtype,
                                                causal):
    """The trainer's three flash kernels at the rows a grid step that
    ``train_tiles`` gives (16 at BERT-base's shape): the step's working
    set fits the VMEM a kernel gets, or this compile raises as the
    chip's would, at the tiles ``train_blocks`` gives past one tile;
    three Mosaic calls; the backward's statistics lie along lanes,
    ``f32[bh,1,T]``, not one number a lane tile, and so does the
    forward's ``lse`` where a step is several rows: a step of one row
    writes it as a column, as it did."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.ops import flash_attention as fa

    b, h, t, d = shape
    hb = fa.train_tiles(b * h, t, t, d, jnp.dtype(dtype).itemsize)
    assert (hb > 1) == (t <= 512)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    def step(q, k, v, do):
        o, lse = fa._fa_forward_pallas(q, k, v, causal, 0.125,
                                       with_lse=True)
        return fa._fa_backward_pallas(q, k, v, o, do, lse, causal, 0.125)

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            text = jax.jit(step).lower(x, x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    lanes, column = f"f32[{b * h},1,{t}]", f"f32[{b * h},1,{t},1]"
    assert lanes in text and (column in text) == (hb == 1)


@pytest.mark.parametrize("shape,dtype", [
    ((128, 128, 12, 64), "bfloat16"),   # bert_base.pretrain_s128
    ((128, 128, 12, 64), "float32"),    # the same without autocast
    ((16, 512, 8, 128), "bfloat16"),    # the longest one-tile sequence
    ((16, 512, 8, 64), "bfloat16"),     # and two heads to its tile
    ((8, 384, 4, 256), "bfloat16"),     # a head two lane tiles wide
], ids=["bert_s128_bf16", "bert_s128_f32", "t512_hd128", "t512_hd64",
        "t384_hd256"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_token_major_flash_kernels_compile_for_v5e(one_chip, shape, dtype,
                                                   causal):
    """The three kernels that index ``(B, T, N x H)`` where it lies, at
    the batch rows a step ``tokens_rows`` gives (8 at BERT-base's shape,
    two heads of 64 each): blocks of whole lane tiles, the lane masks on
    packed bf16 and the step's working set are what Mosaic takes for the
    chip; three calls and nothing of XLA's between the operands and them
    (no transpose, no copy); ``lse`` along lanes, a lane tile's heads
    side by side."""
    from mxnet_tpu.ops import flash_attention as fa

    b, t, n, d = shape
    pair = fa.tokens_lanes(d)[1]
    x = jax.ShapeDtypeStruct((b, t, n * d), jnp.dtype(dtype),
                             sharding=one_chip)

    def step(q, k, v, do):
        o, lse = fa._fa_forward_tokens(q, k, v, n, causal, 0.125,
                                       with_lse=True)
        return fa._fa_backward_tokens(q, k, v, o, do, lse, n, causal, 0.125)

    text = _compiled_without_cache(step, x, x, x, x).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert f"f32[{b},{n // pair},{pair},{t}]" in text
    assert " copy(" not in text and " transpose(" not in text


def _compiled_without_cache(fn, *avals):
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            return jax.jit(fn).lower(*avals).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def test_training_kernels_of_the_latent_expert_cell_compile_for_v5e(one_chip):
    """``joyai_flash.pretrain_s4k``: the three flash kernels at q/k 192 wide
    and v 128, causal, 4 x 32 heads x 4,096 (Mosaic takes the one and a half
    lane tiles as they are: nothing is padded in HBM), and the grouped expert
    feed-forward's backward at 16,384 rows, 8 of 256 experts a row, 16 of 768
    held: one ``grouped_expert_ffn_dx``, three ``grouped_expert_ffn_dw``
    and one ``grouped_expert_ffn_rows`` (the pairs' dX added into the rows'
    order, token tiles of 1,024) under the windows' loop, no every-expert
    intermediate, nothing of ``rows x k`` rows and no scatter of rows of
    the hidden size."""
    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import grouped_ffn

    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k, v, do):
        o, lse = fa._fa_forward_pallas(q, k, v, True, 192 ** -0.5,
                                       with_lse=True)
        return fa._fa_backward_pallas(q, k, v, o, do, lse, True, 192 ** -0.5)

    wide, narrow = sds((4, 32, 4096, 192)), sds((4, 32, 4096, 128))
    text = _compiled_without_cache(attend, wide, wide, narrow,
                                   narrow).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "[128,4096,256]" not in text          # no padded copy of a head

    rows, k, held, h, i = 16384, 8, 16, 2048, 768

    def loss(x, idx, w, wg, wu, wd):
        return grouped_ffn.grouped_expert_ffn(x, idx, w, wg, wu, wd) \
            .astype(jnp.float32).sum()

    compiled = _compiled_without_cache(
        jax.grad(loss, argnums=(0, 2, 3, 4, 5)), sds((rows, h)),
        sds((rows, k), jnp.int32), sds((rows, k), jnp.float32),
        sds((held, h, i)), sds((held, h, i)), sds((held, i, h)))
    text = compiled.as_text()
    names = [ln.split("=")[0].strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("grouped_expert_ffn_dx" in n for n in names) == 1
    assert sum("grouped_expert_ffn_dw" in n for n in names) == 3
    assert sum("grouped_expert_ffn_rows" in n for n in names) == 1
    assert len(names) == 5
    assert not [ln for ln in text.splitlines()
                if " scatter(" in ln and f",{h}]" in ln.split("=")[1][:40]]
    assert f"[{rows},{held},{i}]" not in text
    assert f"[{rows * k}," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("rows,k,held,hidden,width", [
    (512, 8, 128, 2048, 768),     # sdar_30b.chat_decode_sat: a block pass
    (512, 4, 64, 2048, 1536),     # lfm2_24b: its 512 prefill bucket
    (512, 10, 128, 2048, 512),    # qwen3_next: its 512 prefill bucket
    (32768, 8, 16, 6144, 2048),   # glm5.longdoc_prefill: its longest bucket
], ids=["sdar_512x8_of_128", "lfm2_512x4_of_64", "qwen3_next_512x10_of_128",
        "glm5_32768x8_of_16"])
def test_grouped_expert_kernel_compiles_for_v5e(one_chip, rows, k, held,
                                                hidden, width):
    """Mosaic takes ``ops.grouped_ffn`` at the three sparse cells' widths
    (it lives in this file because the described chip is this file's: one
    worker loads the library).  Two whole experts in VMEM, or two width
    tiles of 512 where an expert is 75.5 MB; the program holds the bank as
    it came in and nothing of ``(rows, experts, width)``, the other form's
    intermediate, nor anything of ``rows x k`` rows: where the pairs fit
    one window the rows and their float32 sum stay in VMEM for the call
    (``grouped_expert_ffn_resident``: one kernel, a float32 copy of the
    rows beside it, no gather of rows); where they exceed it (262,144 x
    6,144 would be 3.2 GB) the held pairs go a window at a time under a
    loop, and ``grouped_expert_ffn_rows`` adds a window's rows of 6,144
    into the rows' order (row tiles of 256, token tiles of 512)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.ops import grouped_ffn

    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert grouped_ffn.applicable("tpu", None, rows, k, held, hidden, width)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            compiled = jax.jit(grouped_ffn.grouped_expert_ffn).lower(
                sds((rows, hidden)), sds((rows, k), jnp.int32),
                sds((rows, k), jnp.float32), sds((held, hidden, width)),
                sds((held, hidden, width)), sds((held, width, hidden)),
                sds((rows,), jnp.bool_)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    text = compiled.as_text()
    tm, _ = grouped_ffn.tiles(hidden, width)
    windows = grouped_ffn.rows_form(rows, k, hidden, width) == "kernel"
    names = [ln.split("=")[0].strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(names) == (2 if windows else 1)
    assert sum("grouped_expert_ffn_rows" in n for n in names) == windows
    assert "grouped_expert_ffn" in text
    assert f"[{rows},{held},{width}]" not in text
    bank = (f"[{held},{hidden},{width}]", f"[{held},{width},{hidden}]")
    assert not [ln for ln in text.splitlines()
                if any(b in ln.split("=")[0] for b in bank)
                and (" copy(" in ln or " convert(" in ln
                     or " transpose(" in ln)]
    if windows:
        assert (tm, grouped_ffn.token_rows(rows, hidden)) == (256, 512)
        win = grouped_ffn.window_pairs(rows, k, hidden, tm)
        assert f"f32[{win},{hidden}]" in text       # a window's rows
        assert f"[{rows * k}," not in text and f"[{rows * k}]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9
    else:
        assert "grouped_expert_ffn_resident" in text
        assert f"[{rows * k},{hidden}]" not in text     # no row a pair
        assert f"f32[{rows},{hidden}]" in text          # the rows, once
        assert not [ln for ln in text.splitlines()
                    if " gather(" in ln and f",{hidden}]" in ln.split("=")[1]]


def test_latent_sparse_programs_compile_for_v5e_without_whole_arrays(one_chip):
    """GLM-5's widths (``glm5.longdoc_prefill``; one dense and one expert
    layer: every layer's arrays have the cell's shapes, and a program's
    temporaries are one layer's): the prefill program at the 16,384
    bucket holds no ``(L, L)`` array of scores or of a mask, no ``(rows,
    16, 2048)`` array of every held expert on every row (the product runs
    in chunks of 2,048 rows), and under 1.5 GB of temporaries; the step
    program over 16 slots with tables of 32,768 positions gathers the
    index keys and 2,048 latent rows a slot, never a slot's latent view."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.models import glm_moe_dsa as glm
    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import latent_cache, sparse_select

    lp, slots, nb, bs = 16384, 16, 24576, 16
    cfg = glm.GlmMoeDsaConfig(num_layers=2, first_k_dense=1,
                              experts_held=(0, 16), vocab_size=19360,
                              max_seq_len=32768)

    class Net:                              # never initialized: shapes only
        config = cfg

    dec = glm.GlmDecoder(Net(), 32768)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = dict(layers=[{n: sds(*s) for n, s in
                      glm._layer_param_shapes(cfg, l).items()}
                     for l in range(2)],
             emb=sds(19360, 6144), norm=sds(6144), head=sds(19360, 6144))
    pools = [tuple(sds(*s) for s in latent_cache.pool_shapes(nb, bs, 576, 128))
             for _ in range(2)]
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            prefill = jax.jit(dec._prefill_rows_impl).lower(
                w, sds(1, lp, dtype=jnp.int32),
                sds(1, dtype=jnp.int32)).compile()
            step = jax.jit(dec._step_blocks_impl, donate_argnums=(1,)).lower(
                w, pools, sds(slots, 32768 // bs, dtype=jnp.int32),
                sds(slots, dtype=jnp.int32),
                sds(slots, dtype=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    text = prefill.as_text()
    assert not re.findall(rf"\w+\[(?:\d+,)*{lp},{lp}\]", text)
    assert not re.findall(rf"\w+\[{lp},16,2048\]", text)
    assert re.findall(rf"\w+\[{moe.EVERY_EXPERT_ROWS},16,2048\]", text)
    # a tile's index scores before the heads are summed, at the last extent
    assert f"f32[{sparse_select.QUERY_TILE},32,{lp}]" in text
    assert prefill.memory_analysis().temp_size_in_bytes < 1.5e9
    text = step.as_text()
    # index keys a chunk of positions at a time, never the table's width
    assert re.findall(rf"bf16\[{slots},{sparse_select.SCORE_CHUNK},128\]",
                      text)
    assert not re.findall(rf"bf16\[{slots},32768,128\]", text)
    assert re.findall(rf"bf16\[{slots},2048,640\]", text)      # selected rows
    assert not re.findall(rf"\w+\[{slots},32768,640\]", text)
    assert step.memory_analysis().temp_size_in_bytes < 0.5e9


def test_kv_sparse_programs_compile_for_v5e_without_whole_arrays(one_chip):
    """Keye-VL-2.0's widths (``keye_vl2.longctx_decode_sat``; two layers:
    every layer's arrays have the cell's shapes, and a program's
    temporaries are one layer's): the prefill program at the 32,768 bucket
    holds no ``(L, L)`` array of scores or of a mask and under 2.5 GB of
    temporaries; the step program over 16 slots with tables of 32,768
    positions reads the index keys a chunk at a time and gathers 2,048
    rows of 512 lanes a slot a pool, never a slot's K/V view."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.models import keye_vl2 as keye
    from mxnet_tpu.ops import sparse_select

    lp, slots, nb, bs = 32768, 16, 24576, 16
    cfg = keye.KeyeVl2Config(num_layers=2, max_seq_len=32768)

    class Net:                              # never initialized: shapes only
        config = cfg

    dec = keye.KeyeDecoder(Net(), 32768)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = dict(layers=[{n: sds(*s) for n, s in
                      keye._layer_param_shapes(cfg).items()}
                     for _ in range(2)],
             emb=sds(151936, 2048), norm=sds(2048), head=sds(151936, 2048))
    pools = [(sds(nb, 1, bs, 512), sds(nb, 1, bs, 512), sds(nb, 1, bs, 128))
             for _ in range(2)]
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            prefill = jax.jit(dec._prefill_rows_impl).lower(
                w, sds(1, lp, dtype=jnp.int32),
                sds(1, dtype=jnp.int32)).compile()
            step = jax.jit(dec._step_blocks_impl, donate_argnums=(1,)).lower(
                w, pools, sds(slots, 32768 // bs, dtype=jnp.int32),
                sds(slots, dtype=jnp.int32),
                sds(slots, dtype=jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    text = prefill.as_text()
    assert not re.findall(rf"\w+\[(?:\d+,)*{lp},{lp}\]", text)
    # a tile's index scores before the heads are summed, at the last extent
    assert f"f32[{sparse_select.QUERY_TILE},16,{lp}]" in text \
        or f"f32[1,{sparse_select.QUERY_TILE},16,{lp}]" in text
    assert prefill.memory_analysis().temp_size_in_bytes < 2.5e9
    text = step.as_text()
    assert re.findall(rf"bf16\[{slots},{sparse_select.SCORE_CHUNK},128\]",
                      text)
    assert not re.findall(rf"bf16\[{slots},32768,\d+\]", text)
    assert re.findall(rf"bf16\[{slots},2048,512\]", text)      # selected rows
    assert step.memory_analysis().temp_size_in_bytes < 0.5e9

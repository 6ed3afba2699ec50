"""A selecting K/V cache on the chip (``ops.sparse_select``,
``ops.paged_attention.selected_rows``): the XLA paths the served
programs of ``keye_vl2.longctx_decode_sat`` run (the step's: index keys scored
through the block table in chunks up to the longest live slot, the exact
``lax.top_k`` selection, the selected rows of the K and V pools gathered; the
prefill's: the same set as a mask by bisection, grouped-query attention under
it over rows in order) against the plain form (every key attended under the
sorted selection's mask, K/V repeated for the query heads), at Keye-VL-2.0's
head sizes (32 query / 4 KV heads of 128, an indexer of 16 x 64 that selects
2,048), bf16.  There is no Pallas kernel: these are the programs the cell runs.

Tolerances: the forms read the same set (asserted: the selection is the same
function of the same float32 scores) and differ in the order of their float32
sums and in where the probabilities round to bf16: ``4 * EPS`` of the output's
largest value.
"""
import numpy as np

EPS = 2.0 ** -8
TOPK = 2048
NH, NKV, HD, IH, ID = 32, 4, 128, 16, 64


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.abs(want).max() > 0
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_prefill_tiles_match_the_plain_form_at_16k(parity_record):
    """The last two query tiles of a 16k prompt: every row sees 16k keys and
    reads 2,048 of them; under the mask, and over gathered rows."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import sparse_select as ss
    from mxnet_tpu.ops.attention import masked_attention

    t, rows = 16384, 2 * ss.QUERY_TILE
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(13), 6)
    k = jax.random.normal(ks[0], (1, NKV, t, HD), bf)
    v = jax.random.normal(ks[1], (1, NKV, t, HD), bf)
    keys = jax.random.normal(ks[2], (1, t, ID), bf)
    q = jax.random.normal(ks[3], (1, NH, rows, HD), bf)
    q_idx = jax.random.normal(ks[4], (1, rows, IH, ID), bf)
    w_idx = jax.random.normal(ks[5], (1, rows, IH), bf) * 0.02
    visible = jnp.arange(t)[None, :] <= (t - rows + jnp.arange(rows))[:, None]

    def masked(q, k, v, keys, q_idx, w_idx):
        chosen = ss.select_mask(ss.index_scores(q_idx, w_idx, keys), visible,
                                TOPK)
        return ss.gqa_masked_attention(q, k, v, chosen), chosen

    def gathered(q, k, v, keys, q_idx, w_idx):
        idx, valid = ss.select(ss.index_scores(q_idx, w_idx, keys), visible,
                               TOPK)
        kt, vt = (a[0].transpose(1, 0, 2).reshape(t, NKV * HD) for a in (k, v))
        ctx = ss.gqa_selected_attention(q[0].transpose(1, 0, 2), kt[idx[0]],
                                        vt[idx[0]], valid[0])
        return ctx[None], idx, valid

    def plain(q, k, v, idx, valid):
        return masked_attention(q, k, v, ss.chosen_mask(idx, valid, t)[:, None]) \
            .transpose(0, 2, 1, 3)

    args = (q, k, v, keys, q_idx, w_idx)
    under_mask, chosen = jax.jit(masked)(*args)
    over_rows, idx, valid = jax.jit(gathered)(*args)
    assert bool(valid.all())
    assert bool((chosen == ss.chosen_mask(idx, valid, t)).all())
    want = jax.jit(plain)(q, k, v, idx, valid)
    err = _rel(under_mask, want)
    parity_record("sparse_select", "kv_prefill_tiles_16k", err)
    assert err < 4 * EPS, err
    err = _rel(over_rows, want)
    parity_record("sparse_select", "kv_gathered_tiles_16k", err)
    assert err < 4 * EPS, err


def test_a_whole_prefill_in_tiles_matches_the_plain_form(parity_record):
    """3,072 rows: tiles that score nothing (rows that see no more than 2,048
    positions), tiles that choose, and tiles past the prompt's end left out."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import sparse_select as ss

    t, live = 3072, 2900
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(17), 6)
    q = jax.random.normal(ks[0], (1, NH, t, HD), bf)
    k = jax.random.normal(ks[1], (1, NKV, t, HD), bf)
    v = jax.random.normal(ks[2], (1, NKV, t, HD), bf)
    keys = jax.random.normal(ks[3], (1, t, ID), bf)
    q_idx = jax.random.normal(ks[4], (1, t, IH, ID), bf)
    w_idx = jax.random.normal(ks[5], (1, t, IH), bf) * 0.02
    tiled = jax.jit(lambda *a: ss.kv_causal_attention(
        *a, jnp.asarray([live]), TOPK))(q, k, v, q_idx, w_idx, keys)
    want = jax.jit(lambda *a: ss.kv_plain_causal_attention(*a, TOPK))(
        q, k, v, q_idx, w_idx, keys)
    end = -(-live // ss.QUERY_TILE) * ss.QUERY_TILE
    assert not np.asarray(tiled[:, end:], np.float32).any()
    err = _rel(tiled[:, :end], want[:, :end])
    parity_record("sparse_select", "kv_prefill_3k", err)
    assert err < 4 * EPS, err


def test_step_through_the_block_table_matches_the_plain_form(parity_record):
    """16 slots at 8k to 28k positions, their rows scattered over the three
    pools by a shuffled block table of 32,768 positions; vacant entries hold
    the sentinel, and one slot is vacant."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops import sparse_select as ss
    from mxnet_tpu.ops.attention import masked_attention

    s, bs, t = 16, 16, 32768
    mb = t // bs
    pos = np.linspace(8192, 28671, s).astype(np.int32)
    own = -(-(pos + 1) // bs)
    own[5] = 0                                          # a vacant slot
    nb = int(own.sum()) + 7
    perm = np.random.RandomState(3).permutation(nb)
    tables, start = np.full((s, mb), nb, np.int32), 0
    for i in range(s):
        tables[i, :own[i]] = perm[start:start + own[i]]
        start += own[i]
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    k_pool = jax.random.normal(ks[0], pa.pool_shape(nb, NKV, HD, bs, NKV), bf)
    v_pool = jax.random.normal(ks[1], k_pool.shape, bf)
    i_pool = jax.random.normal(ks[2], ss.index_pool_shape(nb, bs, ID), bf) \
        .at[..., ID:].set(0)
    q = jax.random.normal(ks[3], (s, NH, HD), bf)
    q_idx = jax.random.normal(ks[4], (s, IH, ID), bf)
    w_idx = jax.random.normal(ks[5], (s, IH), bf) * 0.02
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)

    def fast(k_pool, v_pool, i_pool, q, q_idx, w_idx):
        win = pa.window(k_pool, tables, pos, t, False)
        idx, valid = ss.window_select(q_idx, w_idx, i_pool, win, TOPK)
        return ss.gqa_selected_attention(
            q, pa.selected_rows(k_pool, win, idx),
            pa.selected_rows(v_pool, win, idx), valid), idx, valid

    def plain(k_pool, v_pool, q, idx, valid):
        gat = jnp.minimum(tables, nb - 1)
        # a slot at a time: the float32 scores of one are 4 MB a head
        return jax.lax.map(lambda x: masked_attention(
            x[0][None, :, None], x[1][None], x[2][None],
            ss.chosen_mask(x[3][None, None, None], x[4][None, None, None],
                           t))[0, :, 0],
            (q, pa.gathered_view(k_pool, gat, NKV),
             pa.gathered_view(v_pool, gat, NKV), idx, valid))

    got, idx, valid = jax.jit(fast)(k_pool, v_pool, i_pool, q, q_idx, w_idx)
    live = np.asarray(own) > 0
    assert bool(valid[live].all()) and bool((idx <= pos[:, None])[live].all())
    # exact: the 2,048 largest of a row, by an independent count
    keys = np.asarray(pa.gathered_view(i_pool, jnp.minimum(tables, nb - 1), 1)
                      [3, 0, :, :ID], np.float32)
    sc = np.maximum(np.einsum("jd,td->jt", np.asarray(q_idx[3], np.float32),
                              keys), 0)
    row = (np.asarray(w_idx[3], np.float32)[:, None] * sc).sum(0)
    row[int(pos[3]) + 1:] = -np.inf
    mine = np.zeros(t, bool)
    mine[np.asarray(idx[3])] = True
    least = np.sort(row)[-TOPK]
    margin = 1e-3 * np.abs(row[np.isfinite(row)]).max()
    assert mine[row > least + margin].all()
    assert not mine[row < least - margin].any()
    want = jax.jit(plain)(k_pool, v_pool, q, idx, valid)
    err = _rel(np.asarray(got)[live], np.asarray(want)[live])
    parity_record("sparse_select", "kv_step_16_slots_28k", err)
    assert err < 4 * EPS, err

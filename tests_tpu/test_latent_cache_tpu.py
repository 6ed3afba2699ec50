"""``ops.latent_cache`` (and the selection it shares, ``ops.sparse_select``) on the chip: the XLA selection paths the served programs
run (the step's: exact ``lax.top_k`` selection, gathered rows, the absorbed
form; the prefill's: the same set as a mask by bisection, the absorbed form
under it) against
the module's plain form (every key expanded, the selection as a mask), at
GLM-5's head sizes (64 heads of 192 + 64 / 256 over a latent of 512 + 64, an
indexer of 32 x 128 that selects 2,048) and a 16k context, bf16.  There is no
Pallas kernel yet: these are the programs ``glm5.longdoc_prefill`` runs.

Tolerances: the two forms read the same set (asserted: the selection is the
same function of the same float32 scores) and differ in where they round: the
absorbed form rounds ``q W_UK`` and ``sum p c_kv`` to bf16, the expanded one
``k_nope`` and ``v``: ``4 * EPS`` of the output's largest value.
"""
import numpy as np
import pytest

EPS = 2.0 ** -8
T, TOPK = 16384, 2048
NH, DN, DR, DV, RANK, IH, ID = 64, 192, 64, 256, 512, 32, 128


def _inputs(rows):
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    return dict(
        latent=jax.random.normal(ks[0], (1, T, RANK + DR), bf),
        keys=jax.random.normal(ks[1], (1, T, ID), bf),
        q_nope=jax.random.normal(ks[2], (1, rows, NH, DN), bf),
        q_rope=jax.random.normal(ks[3], (1, rows, NH, DR), bf),
        q_idx=jax.random.normal(ks[4], (1, rows, IH, ID), bf),
        w_idx=jax.random.normal(ks[5], (1, rows, IH), bf) * 0.02,
        w_uk=jax.random.normal(ks[6], (NH, DN, RANK), bf) * 0.02,
        w_uv=jax.random.normal(ks[7], (NH, DV, RANK), bf) * 0.02)


def test_top_k_lists_equal_scores_by_position(parity_record):
    """The selection's rule for ties rests on ``lax.top_k``'s order here."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import sparse_select as ss

    scores = jnp.zeros((8, T), jnp.float32).at[:, ::5].set(1.0)
    idx, valid = jax.jit(lambda s: ss.select(s, jnp.ones_like(s, bool),
                                             TOPK))(scores)
    assert bool(valid.all())
    assert (np.asarray(idx) == np.arange(TOPK) * 5).all()
    parity_record("latent_cache", "ties_by_position", 0.0)


def test_prefill_tiles_match_the_plain_form_at_16k(parity_record):
    """The last two query tiles of a 16k prompt: every row sees 16k keys and
    reads 2,048 of them."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import latent_cache as lc
    from mxnet_tpu.ops import sparse_select as ss

    rows = 2 * ss.QUERY_TILE
    a = _inputs(rows)
    at = T - rows
    cols = jnp.arange(T)
    visible = cols[None, :] <= (at + jnp.arange(rows))[:, None]

    def fast(a):
        scores = ss.index_scores(a["q_idx"], a["w_idx"], a["keys"])
        idx, valid = ss.select(scores, visible, TOPK)
        stored = jnp.pad(a["latent"], ((0, 0), (0, 0), (0, 64)))
        got = jax.vmap(lambda s, ix: s[ix])(stored, idx)
        return lc.selected_attention(a["q_nope"], a["q_rope"], got, valid,
                                     a["w_uk"], a["w_uv"], 0.0625), idx, valid

    def plain(a, idx, valid):
        return lc.plain_attention(a["q_nope"], a["q_rope"], a["latent"],
                                  ss.chosen_mask(idx, valid, T), a["w_uk"],
                                  a["w_uv"], 0.0625)

    def masked(a):
        # the prefill's own form: the set as a mask, rows in order
        scores = ss.index_scores(a["q_idx"], a["w_idx"], a["keys"])
        chosen = ss.select_mask(scores, visible, TOPK)
        stored = jnp.pad(a["latent"], ((0, 0), (0, 0), (0, 64)))
        return lc.masked_attention(a["q_nope"], a["q_rope"], stored, chosen,
                                   a["w_uk"], a["w_uv"], 0.0625), chosen

    got, idx, valid = jax.jit(fast)(a)
    assert idx.shape == (1, rows, TOPK) and bool(valid.all())
    under_mask, chosen = jax.jit(masked)(a)
    # the bisections' set is the sort's, to the bit
    assert bool((chosen == ss.chosen_mask(idx, valid, T)).all())
    # exact: the 2,048 largest of each row, by an independent count
    scores = np.asarray(ss.index_scores(a["q_idx"], a["w_idx"], a["keys"]))[0]
    for r in (0, rows - 1):
        row = np.where(np.asarray(visible[r]), scores[r], -np.inf)
        least = np.sort(row)[-TOPK]
        mine = np.zeros(T, bool)
        mine[np.asarray(idx[0, r])] = True
        assert mine[row > least].all() and not mine[row < least].any()
    want = jax.jit(plain)(a, idx, valid)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.abs(want).max() > 0
    err = float(np.abs(got - want).max() / np.abs(want).max())
    parity_record("latent_cache", "gathered_tiles_16k", err)
    assert err < 4 * EPS, err
    under_mask = np.asarray(under_mask, np.float32)
    err = float(np.abs(under_mask - want).max() / np.abs(want).max())
    parity_record("latent_cache", "prefill_tiles_16k", err)
    assert err < 4 * EPS, err


def test_step_through_the_block_table_matches_the_plain_form(parity_record):
    """16 slots at 9k to 16k positions, their rows scattered over a pool by a
    shuffled block table; vacant entries hold the sentinel."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import latent_cache as lc
    from mxnet_tpu.ops import sparse_select as ss
    from mxnet_tpu.ops import paged_attention as pa

    s, bs = 16, 16
    mb = T // bs
    a = _inputs(s)
    pos = np.linspace(0.55 * T, T - 1, s).astype(np.int32)
    own = -(-(pos + 1) // bs)
    nb = int(own.sum()) + 7
    perm = np.random.RandomState(3).permutation(nb)
    tables, start = np.full((s, mb), nb, np.int32), 0
    for i in range(s):
        tables[i, :own[i]] = perm[start:start + own[i]]
        start += own[i]
    shapes = lc.pool_shapes(nb, bs, RANK + DR, ID)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    lat_pool = jax.random.normal(ks[0], shapes[0], jnp.bfloat16) \
        .at[..., RANK + DR:].set(0)
    idx_pool = jax.random.normal(ks[1], shapes[1], jnp.bfloat16)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)

    def fast(lat_pool, idx_pool, a):
        win = pa.window(lat_pool, tables, pos, T, False)
        idx, valid = ss.window_select(a["q_idx"][0], a["w_idx"][0], idx_pool,
                                      win, TOPK)
        return lc.window_attention(a["q_nope"][0], a["q_rope"][0], lat_pool,
                                   win, idx, valid, a["w_uk"], a["w_uv"],
                                   0.0625), idx, valid

    def plain(lat_pool, idx_pool, a, idx, valid):
        gat = jnp.minimum(tables, nb - 1)
        latent = pa.gathered_view(lat_pool, gat, 1)[:, 0, :, :RANK + DR]
        # a slot at a time: the expanded keys of one are 0.9 GB
        return jax.lax.map(lambda x: lc.plain_attention(
            x[0][None, None], x[1][None, None], x[2][None],
            ss.chosen_mask(x[3][None, None], x[4][None, None], T),
            a["w_uk"], a["w_uv"], 0.0625)[0, 0],
            (a["q_nope"][0], a["q_rope"][0], latent, idx, valid))

    got, idx, valid = jax.jit(fast)(lat_pool, idx_pool, a)
    assert bool(valid.all()) and bool((idx <= pos[:, None]).all())
    want = jax.jit(plain)(lat_pool, idx_pool, a, idx, valid)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.abs(want).max() > 0
    err = float(np.abs(got - want).max() / np.abs(want).max())
    parity_record("latent_cache", "step_16_slots_16k", err)
    assert err < 4 * EPS, err

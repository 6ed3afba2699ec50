"""``ops.grouped_ffn.grouped_expert_ffn`` on the chip against
``models.moe.routed_ffn``'s other form (every held expert on every row), one
layer's bank at the two sparse cells' published widths, bf16: SDAR-30B-A3B's
128 experts of 768, 8 a row, and LFM2-24B-A2B's 64 of 1,536, 4 a row behind
sigmoid scores and a choice bias; 512 rows a call (SDAR's block pass, both
models' longest chat prefill bucket) and 397 (its pairs fill no whole row tile):
calls whose pairs fit one window, the rows and their float32 sum in VMEM
(``grouped_expert_ffn_resident``), as is Nemotron 3 Super's 512-row bucket (128
of 512 ``"relu2"`` experts of 2,688 over latent rows of 1,024, 22 a row);
and one chip's 16 of GLM-5's 256 experts of 6,144 x 2,048, 8 a row, at 8,192
rows: an expert walked in width tiles, the held pairs in windows, the padded
end of the bucket left out; beside it the lowered text of the 32,768 bucket,
and that bucket run: three windows of 5,376 pairs whose rows of 6,144 go back
into the rows' order through ``grouped_expert_ffn_rows``.  The trained cell's
layer (16,384 rows, 16 of 256 experts of 768): the forward and all five
gradients of the op against XLA's of the every-expert form.

Tolerance: the two forms route to the bit and differ in where they round (the
one form rounds gate and up to bf16 and applies the weight in bf16; the kernel
keeps float32 to the last cast): ``4 * EPS`` of the output's largest value.
"""
import numpy as np
import pytest

EPS = 2.0 ** -8
H = 2048

BANKS = {"sdar": dict(e=128, k=8, i=768, score="softmax", bias=False),
         "lfm2": dict(e=64, k=4, i=1536, score="sigmoid", bias=True)}


@pytest.mark.parametrize("rows", [512, 397])
@pytest.mark.parametrize("bank", sorted(BANKS))
def test_grouped_kernel_matches_every_expert_on_every_row(bank, rows,
                                                          parity_record,
                                                          monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    b = BANKS[bank]
    e, k, i = b["e"], b["k"], b["i"]
    assert grouped_ffn.applicable("tpu", None, rows, k, e, H, i)
    assert moe.expert_product(rows, k, e, H, i, jnp.bfloat16) \
        == "grouped_kernel"
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, H), bf)
    rw = jax.random.normal(keys[1], (e, H), bf) * 0.02
    wg = jax.random.normal(keys[2], (e, H, i), bf) * 0.02
    wu = jax.random.normal(keys[3], (e, H, i), bf) * 0.02
    wd = jax.random.normal(keys[4], (e, i, H), bf) * 0.02
    bias = jax.random.normal(keys[5], (e,), jnp.float32) * 0.1 \
        if b["bias"] else None
    live = jnp.arange(rows) % 7 != 0
    # the second half of the bank, as a chip that holds half would run it
    half = (e // 2, e // 2)

    def run(held):
        # the bank as arguments: constants of 1.2 GB compile for minutes
        sl = slice(None) if held is None else slice(held[0], None)
        return jax.jit(lambda x, rw, wg, wu, wd: moe.routed_ffn(
            x, rw, wg, wu, wd, k, score=b["score"], choice_bias=bias,
            experts_held=held, live=live))(x, rw, wg[sl], wu[sl], wd[sl])

    got = {held: run(held) for held in (None, half)}
    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_product", lambda *a: "every_expert")
        want = {held: run(held) for held in (None, half)}
    for held in (None, half):
        (y, counts), (yw, cw) = got[held], want[held]
        assert (np.asarray(counts) == np.asarray(cw)).all()
        assert int(counts.sum()) == int(live.sum()) * k
        y, yw = np.asarray(y, np.float32), np.asarray(yw, np.float32)
        assert np.isfinite(y).all() and np.abs(yw).max() > 0
        # a row no request owns: left out by the kernel, zero
        owned = np.asarray(live)
        assert not y[~owned].any()
        y, yw = y[owned], yw[owned]
        err = float(np.abs(y - yw).max() / np.abs(yw).max())
        parity_record("grouped_expert_ffn",
                      f"{bank}_{rows}_{'all' if held is None else 'half'}",
                      err)
        assert err < 4 * EPS, (bank, rows, held, err)


#: the served calls whose pairs fit one window, 512 rows each: a bank's
#: router ``e`` experts of which ``held`` lie here, ``latent`` the width the
#: experts multiply in where it is not the router's ``h``
SERVED = {
    "sdar": dict(e=128, held=128, k=8, h=2048, latent=None, i=768,
                 score="softmax", kind="swiglu"),
    "lfm2": dict(e=64, held=64, k=4, h=2048, latent=None, i=1536,
                 score="sigmoid", kind="swiglu"),
    "nemotron3_super": dict(e=512, held=128, k=22, h=4096, latent=1024,
                            i=2688, score="sigmoid", kind="relu2"),
}


@pytest.mark.parametrize("rows", [512, 128])
@pytest.mark.parametrize("bank", sorted(SERVED))
def test_the_resident_form_at_the_served_calls(bank, rows, parity_record,
                                               monkeypatch):
    """A call whose pairs fit one window keeps its rows and their float32
    sum in VMEM (``grouped_expert_ffn_resident``): against every held
    expert on every row at three served banks, 512 rows a call (a block
    pass, the longest chat prefill bucket) and 128 (a step of 128 slots:
    under ``GROUPED_MIN_ROWS``, so the form is asked for by name),
    and the compiled program holds nothing of ``rows x k`` rows of the
    hidden width: no gather of them, no float32 pairs."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    b = SERVED[bank]
    e, held, k, i = b["e"], b["held"], b["k"], b["i"]
    wide, h = b["h"], b["latent"] or b["h"]
    relu2 = b["kind"] == "relu2"
    assert grouped_ffn.rows_form(rows, k, h, i) == "resident"
    assert (moe.expert_product(rows, k, held, h, i, jnp.bfloat16)
            == "grouped_kernel") == (rows >= grouped_ffn.GROUPED_MIN_ROWS)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, wide), bf)
    lat = jax.random.normal(keys[5], (rows, h), bf) if h != wide else None
    rw = jax.random.normal(keys[1], (e, wide), bf) * 0.02
    wg = None if relu2 else jax.random.normal(keys[2], (held, h, i), bf) * 0.02
    wu = jax.random.normal(keys[3], (held, h, i), bf) * 0.02
    wd = jax.random.normal(keys[4], (held, i, h), bf) * 0.02
    live = jnp.arange(rows) % 7 != 0

    def build():
        # traced where it is called: the form is read once a program
        return jax.jit(lambda x, lat, rw, wg, wu, wd: moe.routed_ffn(
            x, rw, wg, wu, wd, k, score=b["score"], experts_held=(0, held),
            live=live, kind=b["kind"], rows=lat)[0])

    with monkeypatch.context() as patch:
        # (128 rows lie under ``GROUPED_MIN_ROWS``: asked for by name)
        patch.setattr(moe, "expert_product", lambda *a: "grouped_kernel")
        fn = build()
        text = fn.lower(x, lat, rw, wg, wu, wd).compile().as_text()
        y = fn(x, lat, rw, wg, wu, wd)
    assert "grouped_expert_ffn_resident" in text
    assert f"[{rows * k},{h}]" not in text, "an array of every pair's row"
    assert f"[{rows},{held},{i}]" not in text
    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_product", lambda *a: "every_expert")
        yw = build()(x, lat, rw, wg, wu, wd)
    y, yw = np.asarray(y, np.float32), np.asarray(yw, np.float32)
    owned = np.asarray(live)
    assert np.isfinite(y).all() and np.abs(yw).max() > 0
    assert not y[~owned].any()
    err = float(np.abs(y[owned] - yw[owned]).max() / np.abs(yw[owned]).max())
    parity_record("grouped_expert_ffn", f"{bank}_{rows}_resident", err)
    assert err < 4 * EPS, (bank, rows, err)


def test_a_wide_bank_that_holds_a_part_of_the_router_at_a_long_prefill(
        parity_record, monkeypatch):
    """GLM-5's routed layer as ``glm5.longdoc_prefill`` runs it, 8,192 rows
    of which the last 2,192 belong to no request: the kernel (width tiles
    of 512, 256 rows a visit, windows of 5,376 held pairs) against every
    held expert on every row in chunks of 2,048, on the rows a request
    owns; the others zero; and the counts alike."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    rows, e, held, k, h, i = 8192, 256, 16, 8, 6144, 2048
    assert grouped_ffn.tiles(h, i) == (256, 512)
    assert grouped_ffn.window_pairs(rows, k, h, 256) == 5376
    assert moe.expert_product(rows, k, held, h, i, jnp.bfloat16) \
        == "grouped_kernel"
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, h), bf)
    rw = jax.random.normal(keys[1], (e, h), bf) * 0.02
    wg = jax.random.normal(keys[2], (held, h, i), bf) * 0.02
    wu = jax.random.normal(keys[3], (held, h, i), bf) * 0.02
    wd = jax.random.normal(keys[4], (held, i, h), bf) * 0.02
    bias = jax.random.normal(keys[5], (e,), jnp.float32) * 0.1
    live = jnp.arange(rows) < 6000

    def run():
        return jax.jit(lambda x, rw, wg, wu, wd: moe.routed_ffn(
            x, rw, wg, wu, wd, k, score="sigmoid", choice_bias=bias,
            scale=2.5, experts_held=(0, held), live=live))(x, rw, wg, wu, wd)

    y, counts = run()
    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_product", lambda *a: "every_expert")
        yw, cw = run()
    assert (np.asarray(counts) == np.asarray(cw)).all()
    assert int(counts.sum()) == 6000 * k
    y, yw = np.asarray(y, np.float32), np.asarray(yw, np.float32)
    assert np.isfinite(y).all() and np.abs(yw[:6000]).max() > 0
    assert not y[6000:].any()
    err = float(np.abs(y[:6000] - yw[:6000]).max() / np.abs(yw[:6000]).max())
    parity_record("grouped_expert_ffn", "glm5_8192_16_of_256", err)
    assert err < 4 * EPS, err


def test_the_longest_buckets_windows_go_back_through_the_rows_kernel(
        parity_record, monkeypatch):
    """32,768 rows, the last quarter nobody's: some 12,288 held pairs in
    three windows of 5,376, token tiles of 512 rows of 6,144."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    rows, e, held, k, h, i = 32768, 256, 16, 8, 6144, 2048
    assert grouped_ffn.window_pairs(rows, k, h, 256) == 5376
    assert grouped_ffn.token_rows(rows, h) == 512
    keys = jax.random.split(jax.random.PRNGKey(13), 5)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, h), bf)
    rw = jax.random.normal(keys[1], (e, h), bf) * 0.02
    wg = jax.random.normal(keys[2], (held, h, i), bf) * 0.02
    wu = jax.random.normal(keys[3], (held, h, i), bf) * 0.02
    wd = jax.random.normal(keys[4], (held, i, h), bf) * 0.02
    live = jnp.arange(rows) < 24576

    def run():
        return jax.jit(lambda x, rw, wg, wu, wd: moe.routed_ffn(
            x, rw, wg, wu, wd, k, score="sigmoid", scale=2.5,
            experts_held=(0, held), live=live))(x, rw, wg, wu, wd)

    y, counts = run()
    with monkeypatch.context() as patch:
        patch.setattr(moe, "expert_product", lambda *a: "every_expert")
        yw, cw = run()
    assert (np.asarray(counts) == np.asarray(cw)).all()
    assert 2 * 5376 < int(counts[:held].sum()) <= 3 * 5376
    y, yw = np.asarray(y, np.float32), np.asarray(yw, np.float32)
    assert np.isfinite(y).all() and not y[24576:].any()
    err = float(np.abs(y[:24576] - yw[:24576]).max()
                / np.abs(yw[:24576]).max())
    parity_record("grouped_expert_ffn", "glm5_32768_16_of_256", err)
    assert err < 4 * EPS, err


def test_no_array_of_every_pair_at_the_longest_bucket():
    """32,768 rows x 8 are 262,144 pairs, of which this chip holds a
    sixteenth on average: the lowered program has one kernel under a loop
    and no array of 262,144 rows of 6,144 (3.2 GB in bfloat16), nor one of
    262,144 sorted keys."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import grouped_ffn

    rows, held, k, h, i = 32768, 16, 8, 6144, 2048
    bf = jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    compiled = jax.jit(grouped_ffn.grouped_expert_ffn).lower(
        sds((rows, h), bf), sds((rows, k), jnp.int32),
        sds((rows, k), jnp.float32), sds((held, h, i), bf),
        sds((held, h, i), bf), sds((held, i, h), bf),
        sds((rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "grouped_expert_ffn_rows" in text
    assert " while(" in text
    assert f"[{rows * k}," not in text and f"[{rows * k}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9


def test_grouped_backward_matches_xla_at_the_training_cells_shapes():
    """``joyai_flash.pretrain_s4k``'s expert layer: 16,384 rows, 8 of 256
    experts a row, 16 of 768 held, bf16: ``jax.grad`` through the kernels
    (``grouped_expert_ffn_dx``, ``grouped_expert_ffn_dw``) against XLA's
    gradient of the every-expert form in 2,048-row chunks: dX, the combine
    weights through the router, and the three banks."""
    import jax
    import jax.numpy as jnp
    from unittest import mock

    from mxnet_tpu.models import moe

    rows, e, held, k, i = 16384, 256, 16, 8, 768
    assert moe.expert_product(rows, k, held, H, i, jnp.bfloat16) \
        == "grouped_kernel"
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, H), bf)
    rw = jax.random.normal(keys[1], (e, H), jnp.float32) * 0.02
    bank = tuple(jax.random.normal(kk, s, bf) * 0.02 for kk, s in zip(
        keys[2:5], ((held, H, i), (held, H, i), (held, i, H))))
    dy = jax.random.normal(keys[5], (rows, H), bf)

    def grads(form):
        def loss(x, rw, *bank):
            with mock.patch.object(moe, "expert_product", lambda *a: form):
                y, _ = moe.routed_ffn(x, rw, *bank, k, score="sigmoid",
                                      scale=2.5, experts_held=(0, held))
            return (y.astype(jnp.float32) * dy.astype(jnp.float32)).sum()
        return jax.block_until_ready(
            jax.jit(jax.grad(loss, argnums=range(5)))(x, rw, *bank))

    got, want = grads("grouped_kernel"), grads("every_expert")
    for name, a, w in zip(("dx", "drouter", "dgate", "dup", "ddown"),
                          got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - w) ** 2))
                        / np.sqrt(np.mean(w ** 2)))
        assert rel_rms <= 2.0 ** -5, (name, rel_rms)


def test_the_op_and_its_five_gradients_at_the_training_cells_shapes(
        parity_record):
    """The op alone at ``joyai_flash.pretrain_s4k``'s layer, one window of
    16,384 pairs and token tiles of 1,024: its result, dX, the combine
    weights' gradient (N, k) itself, and the three banks', against XLA's of
    every held expert on every row in chunks of 2,048 under the combine
    matrix the same ids and weights make."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import moe
    from mxnet_tpu.ops import grouped_ffn

    rows, e, held, k, i = 16384, 256, 16, 8, 768
    assert grouped_ffn.window_pairs(rows, k, H, 128) == 16384
    assert grouped_ffn.token_rows(rows, H) == 1024
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(keys[0], (rows, H), bf)
    rw = jax.random.normal(keys[1], (e, H), bf) * 0.02
    bank = tuple(jax.random.normal(kk, s, bf) * 0.02 for kk, s in zip(
        keys[2:5], ((held, H, i), (held, H, i), (held, i, H))))
    dy = jax.random.normal(keys[5], (rows, H), jnp.float32)
    idx, w = jax.jit(lambda x, rw: moe.route(x, rw, k, "sigmoid",
                                             scale=2.5))(x, rw)

    def kernel(x, w, *bank):
        return grouped_ffn.grouped_expert_ffn(x, idx, w, *bank)

    def every(x, w, wg, wu, wd):
        comb = jnp.where(idx[:, :, None] == jnp.arange(held),
                         w[:, :, None], 0.0).sum(1)

        def chunk(c):
            xc, cc = c
            g = jnp.einsum("nh,ehi->nei", xc, wg)
            u = jnp.einsum("nh,ehi->nei", xc, wu)
            act = g * jax.nn.sigmoid(g) * u * cc.astype(xc.dtype)[:, :, None]
            return jnp.einsum("nei,eih->nh", act, wd)
        return jax.lax.map(chunk, (x.reshape(-1, 2048, H),
                                   comb.reshape(-1, 2048, held))) \
            .reshape(rows, H)

    def both(fn):
        def loss(*a):
            y = fn(*a)
            return (y.astype(jnp.float32) * dy).sum(), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(5), has_aux=True))(x, w, *bank)
        return jax.block_until_ready((y,) + grads)

    got, want = both(kernel), both(every)
    for name, a, b in zip(("y", "dx", "dweights", "dgate", "dup", "ddown"),
                          got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        rel_rms = float(np.sqrt(np.mean((a - b) ** 2))
                        / np.sqrt(np.mean(b ** 2)))
        parity_record("grouped_expert_ffn", f"joyai_16384_{name}", rel_rms)
        assert rel_rms <= 2.0 ** -5, (name, rel_rms)
    # a choice no held expert computes has no say in the weights' gradient
    away = np.asarray(idx) >= held
    assert not np.asarray(got[2])[away].any()

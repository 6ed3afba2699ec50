"""``ops.grouped_ffn.grouped_expert_ffn`` on the chip against
``models.moe.routed_ffn``'s other form (every held expert on every row), one
layer's bank at the two sparse cells' published widths, bf16: SDAR-30B-A3B's
128 experts of 768, 8 a row, and LFM2-24B-A2B's 64 of 1,536, 4 a row behind
sigmoid scores and a choice bias; 512 rows a call (SDAR's block pass, both
models' longest chat prefill bucket) and 397 (its pairs fill no whole row tile).

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
        err = float(np.abs(y - yw).max() / np.abs(yw).max())
        parity_record("grouped_expert_ffn",
                      f"{bank}_{rows}_{'all' if held is None else 'half'}",
                      err)
        assert err < 4 * EPS, (bank, rows, held, err)

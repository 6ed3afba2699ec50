"""Nemotron-H's state-space kernel on the chip at the published sizes
(``nemotron3_super.chat_decode_sat``): the selective scan's two served forms
(``mxnet_tpu/ops/ssm_scan.py``) against the module's plain recurrence, and what
a donated step holds of the pool.

Shapes: 128 slots of 128 heads of (64, 128) float32 in 8 groups, stored two
heads a lane row, ``(64, 128, 128)`` (4 MiB a slot a layer, 512 MiB a pool); a
512-row prompt, whole and 397 rows true in the 512
bucket.

Tolerances.  Float32 on both sides; the kernel's lane sums run in another order
than the recurrence's reduce, the chunked scan's through products at
``Precision.HIGHEST``: ``1e-4`` of the largest value after 512 tokens.

``chiprun_out/ssm_scan_tpu.json`` keeps the forms' times: the kernel's and the
XLA form's seconds a call on the whole pool (donated, as a step program holds
it), and the chunked scan's.
"""
import json
import os
import time

import numpy as np
import pytest

SLOTS, HEADS, P, N, GROUPS = 128, 128, 64, 128, 8
FACTS = {}


def _inputs(key, lead):
    """Scan inputs with ``lead`` leading axes before the heads: steps of
    0.001 to 0.1 (softplus of the published initialiser's range and a
    projection's noise), rates on (1, 16)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[1], lead + (HEADS,),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    return (jax.random.normal(ks[0], lead + (HEADS, P)), dt,
            -jax.random.uniform(ks[2], (HEADS,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], lead + (GROUPS, N)),
            jax.random.normal(ks[4], lead + (GROUPS, N)),
            jax.random.normal(ks[5], (HEADS,)))


def _seconds(fn, *args, reps=10):
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return float(np.median(times))


def _keep():
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "ssm_scan_tpu.json"), "w") as f:
        json.dump(FACTS, f, indent=1)


def test_step_kernel_matches_the_recurrence_and_reads_the_pool_once(
        parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import ssm_scan as ss

    assert ss.step_applicable("tpu", None, HEADS, P, N, groups=GROUPS)
    assert ss.step_form(HEADS, P, N, GROUPS) == "step_kernel"
    plain = jax.random.normal(jax.random.PRNGKey(1), (SLOTS, HEADS, P, N))
    pool = ss.to_stored(plain, GROUPS)
    assert pool.shape == (SLOTS, 64, 128, 128)
    x, dt, A, B, C, D = _inputs(jax.random.PRNGKey(2), (SLOTS,))
    live = jnp.arange(SLOTS) % 5 != 0
    want_y, want_s = jax.jit(ss._one_token)(
        plain, x, jnp.where(live[:, None], dt, 0.0), A, B, C, D)
    want_s = ss.to_stored(want_s, GROUPS)
    del plain
    for name, kernel in (("kernel", True), ("xla", False)):
        y, s = jax.jit(lambda pool, live, kernel=kernel: ss.step(
            pool, x, dt, A, B, C, D, live=live, kernel=kernel))(pool, live)
        for got, want, what in ((y, want_y, "y"), (s, want_s, "state")):
            got, want = np.asarray(got), np.asarray(want)
            err = float(np.abs(got - want).max() / np.abs(want).max())
            parity_record("ssm_scan", f"step_{name}_{what}", err)
            assert err < 1e-4, (name, what, err)
        # a slot this step does not own keeps its state to the bit
        assert np.array_equal(np.asarray(s)[::5], np.asarray(pool)[::5])
    # donated, the kernel's program holds no second pool
    donated = jax.jit(lambda pool: ss.step(pool, x, dt, A, B, C, D,
                                           kernel=True), donate_argnums=0)
    comp = donated.lower(pool).compile()
    assert "ssm_state_step" in comp.as_text()
    assert comp.memory_analysis().temp_size_in_bytes < pool.nbytes // 8
    # timed as the step program holds the pool: donated and handed on
    nbytes = 2 * pool.nbytes
    for name, kernel in (("kernel", True), ("xla", False)):
        fn = jax.jit(lambda pool, live, kernel=kernel: ss.step(
            pool, x, dt, A, B, C, D, live=live, kernel=kernel),
            donate_argnums=0)
        held = pool + 0.0
        times = []
        for _ in range(12):
            t = time.perf_counter()
            _y, held = fn(held, live)
            jax.block_until_ready(held)
            times.append(time.perf_counter() - t)
        sec = float(np.median(times[2:]))
        FACTS[f"step_{name}_s"] = sec
        FACTS[f"step_{name}_gb_per_s"] = nbytes / sec / 1e9
    print("ssm_scan step:", json.dumps(FACTS))
    _keep()


@pytest.mark.parametrize("true_rows", [512, 397])
def test_chunk_scan_matches_the_recurrence_over_a_512_row_prompt(
        true_rows, parity_record):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import ssm_scan as ss

    x, dt, A, B, C, D = _inputs(jax.random.PRNGKey(3), (1, 512))
    live = jnp.arange(512)[None] < true_rows
    y, s = jax.jit(lambda live: ss.chunk_scan(x, dt, A, B, C, D, live=live))(
        live)
    cut = lambda a: a[:, :true_rows]                          # noqa: E731
    want_y, want_s = jax.jit(ss.recurrence)(cut(x), cut(dt), A, cut(B),
                                            cut(C), D)
    for got, want, what in ((y[:, :true_rows], want_y, "y"),
                            (s, want_s, "state")):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all()
        err = float(np.abs(got - want).max() / np.abs(want).max())
        parity_record("ssm_scan", f"scan_{true_rows}_{what}", err)
        assert err < 1e-4, (what, err)
    if true_rows == 512:
        FACTS["scan_512_rows_s"] = _seconds(
            jax.jit(lambda: ss.chunk_scan(x, dt, A, B, C, D)))
        FACTS["recurrence_512_rows_s"] = _seconds(
            jax.jit(lambda: ss.recurrence(x, dt, A, B, C, D)), reps=3)
        print("ssm_scan scan:", json.dumps(FACTS))
        _keep()

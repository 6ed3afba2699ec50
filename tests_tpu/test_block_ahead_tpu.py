"""The block tick a pass ahead of its bookkeeping, on the chip
(``serving/lanes.py`` ``DecodeLane._tick`` over a block decoder's
``dispatch_step`` / ``fetch_step``): SDAR-30B-A3B's published widths and the
six layers of ``sdar_30b.chat_decode_sat`` (bf16, 8.7 GB), 16 slots.

Sixteen requests through the lanes, every pass but a stretch's first queued
before the one ahead of it is fetched: each request's tokens and every commit
``(position, token, pass)`` are those of the serial loop (``engine.step()``,
each pass fetched and booked before the next is dispatched, the same sixteen
in the same slots), and in a short trace of the stretch where all sixteen
decode and nothing waits the device never rests 3 ms between two passes (the
serial tick's gap was the fetch's tail, the booking, five uploads and the
dispatch: 7-8 ms a tick at 128 slots, ``PERF.md`` section 6, PR 42).
"""
import glob
import os
import sys
import time

import numpy as np

SLOTS, MAX_LEN, BS, LAYERS = 16, 512, 16, 6
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _net():
    import mxnet_tpu as mx
    from mxnet_tpu.models.sdar import SdarMoeConfig, SdarMoeForCausalLM

    mx.random.seed(5)
    net = SdarMoeForCausalLM(SdarMoeConfig(
        hidden_size=2048, moe_intermediate_size=768, num_layers=LAYERS,
        num_heads=32, num_kv_heads=4, head_dim=128, vocab_size=151936,
        max_seq_len=MAX_LEN, rope_theta=1e6, norm_eps=1e-6, num_experts=128,
        num_experts_per_tok=8, norm_topk_prob=True, block_length=4,
        denoising_steps=4, confidence_threshold=0.9, mask_token_id=151669))
    net.cast("bfloat16")
    net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.init.Normal(0.02))
    return net


def _taken(tick, slot, n_prompt, n_new, out, commits):
    """What ``DecodeLane._book_blocks`` takes of a pass for one request ->
    whether it committed the last of its positions."""
    for j in np.flatnonzero(tick.commit[slot]):
        pos, tok = int(tick.pos0[slot]) + int(j), int(tick.ids[slot, j])
        commits.append((pos, tok, int(tick.step[slot])))
        if pos - n_prompt < n_new:
            out[pos - n_prompt] = tok
    return len(out) == n_new


def _serial(eng, jobs, slots):
    """The serial loop over the same requests in the same slots."""
    for slot in range(eng.num_slots):
        eng.clear_slot(slot)
    got = {}
    for (prompt, n_new), slot in zip(jobs, slots):
        t0 = len(prompt)
        lb = max(32, 1 << (t0 - 1).bit_length())
        ids = np.zeros((1, lb), np.int32)
        ids[0, :t0] = prompt
        t0s = np.asarray([t0], np.int32)
        toks, rows = eng.prefill_rows(ids, t0s)
        first, _counts = eng.split_fetch(np.asarray(toks), 1)
        blocks = list(range(slot * eng.max_blocks, (slot + 1) * eng.max_blocks))
        eng.commit_rows(rows, np.asarray([slot]), [blocks], t0s, first)
        got[slot] = (t0, n_new, {}, [])
    live = set(got)
    while live:
        tick = eng.step(sorted(live))
        for slot in sorted(live):
            if _taken(tick, slot, *got[slot]):
                live.discard(slot)
                eng.clear_slot(slot)
    return {slot: ([out[i] for i in range(n)], commits)
            for slot, (_t0, n, out, commits) in got.items()}


def test_sixteen_requests_run_ahead_equal_the_serial_loop(tmp_path):
    import jax

    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerConfig
    from mxnet_tpu.telemetry import tracing

    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import trace_reduce

    rs = np.random.RandomState(42)
    jobs = [(rs.randint(1, 151000, size=n).astype(np.int32), m)
            for n, m in zip(rs.randint(20, 120, size=SLOTS),
                            rs.randint(200, 300, size=SLOTS))]
    srv = serving.GenerativeServer(_net(), ServerConfig(
        max_batch=1, max_length=MAX_LEN, min_length=32, num_slots=SLOTS,
        block_size=BS))
    with srv:
        # every program compiled before the stretch that is traced: the
        # prompts' three buckets, the scatter, the pass
        for n in (25, 50, 100):
            srv.generate(rs.randint(1, 151000, size=n), max_new_tokens=5)
        while srv.replicas[0].decode._flight is not None:
            time.sleep(0.001)
        warm = srv.engine.compiled_signatures()
        since = time.perf_counter()
        futs = [srv.submit(p, max_new_tokens=n) for p, n in jobs]
        while any(f.request.first_tick is None for f in futs):
            time.sleep(0.001)
        # all sixteen decode and nothing waits, 250 passes and more to go
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        traced = [time.perf_counter()]
        time.sleep(0.4)
        traced.append(time.perf_counter())
        jax.profiler.stop_trace()
        outs = [f.result(600) for f in futs]
        assert srv.engine.compiled_signatures() == warm
        assert srv.engine._step._cache_size() == 1
    ticks = tracing.lane_log("decode.tick", since=since)
    assert sum(t["ahead"] for t in ticks) >= 0.8 * len(ticks)
    inside = [t for t in ticks if traced[0] <= t["t_tok"] <= traced[1]]
    assert len(inside) >= 10
    assert all(t["ahead"] and t["n_active"] == SLOTS for t in inside)
    assert srv.stats()["failed"] == 0

    reqs = [f.request for f in futs]
    want = _serial(srv.engine, jobs, [r.slot for r in reqs])
    for (p, n), req, out in zip(jobs, reqs, outs):
        tokens, commits = want[req.slot]
        assert out[:len(p)].tolist() == p.tolist()
        assert out[len(p):].tolist() == tokens
        assert req.commits == commits

    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    chip, = trace_reduce.reduce(trace_reduce.load(path))["chips"].values()
    passes = chip["modules"]["jit__step_fn"]
    assert len(passes) >= 10
    # between the trace's first and last device event: no rest of 3 ms
    assert chip["longest_gaps"][0][1] < 3e-3, chip["longest_gaps"]
    assert chip["idle_s"] < 0.03 * (chip["busy_s"] + chip["idle_s"])

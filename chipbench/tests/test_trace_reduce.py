"""``trace_reduce`` against two small traces recorded on a v5e (one chip: a
jitted matmul plus the flash-attention kernels forward and backward, five
dispatches with a fetch after each; four chips: the same plus a data-parallel
gradient with its all-reduce)."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")


def test_interval_arithmetic():
    u = tr.union([(0, 2, "a"), (1, 3, "b"), (5, 6, "c"), (6, 6, "empty")])
    assert u == [(0, 3), (5, 6)] and tr.total(u) == 4
    assert tr.gaps(u, -1, 7) == [(-1, 0), (3, 5), (6, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 1), (4, 6)], []) == [(0, 1), (4, 6)]
    by, named = tr.attribute([(3, 5), (6, 7)], [(2.5, 5.5, "bench.fetch", "t"),
                                                (0, 10, tr.WINDOW_SPAN, "t")])
    assert by == {"bench.fetch": 2, "unspanned": 1}
    assert named[0] == ("bench.fetch", 2)


def test_names():
    op = ('%all-reduce.3 = bf16[8]{0} all-reduce(bf16[8]{0} %fusion), channel_id=1')
    assert tr.short_name(op) == "all-reduce.3" and tr.is_collective(op)
    assert not tr.is_collective("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)")
    fwd = ('%jvp__.1 = (bf16[48,128,64]{2,1,0}, f32[48,128,1]{2,1,0}) custom-call('
           'bf16[48,128,64]{2,1,0} %a), custom_call_target="tpu_custom_call"')
    assert tr.is_mosaic(fwd) and tr.mosaic_kind(fwd) == "flash_fwd"


def test_one_chip_trace():
    s = tr.reduce(tr.load(os.path.join(DATA, "probe_1chip.xplane.pb")))
    assert list(s["chips"]) == [0]
    c = s["chips"][0]
    # five executions of about 82 us each; operations cannot outlast programs
    mods = c["modules"]["jit_probe_step"]
    assert len(mods) == 5 and 3.5e-4 < sum(mods) < 4.5e-4
    assert 0 < c["busy_s"] <= sum(mods) * 1.001
    assert c["busy_s"] + c["idle_s"] == pytest.approx(s["window_s"], rel=1e-9)
    assert {k: len(v) for k, v in c["mosaic"].items()} == \
        {"flash_fwd": 5, "flash_dkv": 5, "flash_dq": 5}
    # the device idles while the host fetches; the gaps say so
    assert max(c["idle_by_span"], key=c["idle_by_span"].get) == "bench.fetch"
    assert c["collective_s"] == 0
    b = tr.breakdown(s)
    assert 3 <= len(b["device_ops"]) <= 10 and b["device_ops"][0][1] >= b["device_ops"][1][1]
    assert b["idle_gaps"][0][0] == "bench.fetch"


def test_four_chip_trace():
    s = tr.reduce(tr.load(os.path.join(DATA, "probe_4chip.xplane.pb")))
    assert sorted(s["chips"]) == [0, 1, 2, 3]
    for c in s["chips"].values():
        assert len(c["modules"]["jit_dp_step"]) == 5
        # nothing computes while this all-reduce runs: all of it is exposed
        assert 1.5e-4 < c["collective_s"] < 2.5e-4
        assert c["collective_exposed_s"] == pytest.approx(c["collective_s"])
        assert c["busy_s"] > c["collective_s"]
    assert tr.breakdown(s)["device_ops"][0][0] == "all-reduce"

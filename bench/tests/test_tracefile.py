"""The reduction from trace intervals to metrics, on a hand-made trace and
on a small trace recorded on a TPU v5e (``data/trace_small.json``: the
first device operations of a traced window of cell xlstm350m.train1)."""
import os
import types

import pytest

from bench import tracefile
from bench.metrics import device_idle_share, grad_sync_ms

DATA = os.path.join(os.path.dirname(__file__), "data")

HAND = {
    "ops": {
        0: [["fusion.1", 100, 200], ["while.3", 150, 400], ["collective-permute-start.2", 500, 520],
            ["collective-permute-done.2", 600, 700], ["fusion.9", 950, 1200]],
        1: [["fusion.1", 100, 300], ["all-reduce.4", 300, 500]],
    },
    "spans": [["bench.window", 100, 1100], ["bench.wait", 400, 1000], ["bench.feed", 700, 760]],
}


def _ctx(trace, steps=1):
    lo, hi = tracefile.window(trace)
    return types.SimpleNamespace(trace=trace, lo=lo, hi=hi, steps=steps, chips=len(trace["ops"]),
                                 log=lambda _: None)


def test_union_clips_and_merges():
    assert tracefile.merged([(150, 400), (100, 200), (950, 1200)], 100, 1100) == [[100, 400], [950, 1100]]
    assert tracefile.busy_ns(HAND, 0, 100, 1100) == 300 + 20 + 100 + 150
    assert tracefile.busy_ns(HAND, 1, 100, 1100) == 400


def test_idle_share_and_gaps():
    ctx = _ctx(HAND)
    # chip 0 idle 430 of 1000 ns, chip 1 idle 600 of 1000 ns
    assert device_idle_share.read(ctx) == pytest.approx((43.0 + 60.0) / 2)
    gaps = tracefile.idle_gaps(HAND, 0, 100, 1100)
    assert gaps == [(400, 500), (520, 600), (700, 950)]
    named = tracefile.named_gaps(HAND, 0, 100, 1100, k=2)
    assert named == [["bench.wait", 250e-9], ["bench.wait", 100e-9]]


def test_collective_time_by_name():
    ctx = _ctx(HAND, steps=2)
    # chip 0: 20 + 100 ns of collective-permute; chip 1: 200 ns of all-reduce
    assert grad_sync_ms.read(ctx) == pytest.approx((120 + 200) / 2 / 1e6 / 2)
    quiet = {"ops": {0: [["fusion.1", 100, 200]]}, "spans": [["bench.window", 0, 300]]}
    assert grad_sync_ms.read(_ctx(quiet)) is None


def test_top_ops():
    top = tracefile.top_ops(HAND, 100, 1100, k=2)
    # fusion.1: 100 + 200 ns over two chips; while.3: 250 ns on one
    assert top == [["fusion.1", 300 / 2 / 1e9], ["while.3", 250 / 2 / 1e9]]


def test_recorded_trace():
    path = os.path.join(DATA, "trace_small.json")
    trace = tracefile.load(path)
    lo, hi = tracefile.window(trace)
    end = max(e for ev in trace["ops"].values() for _, _, e in ev)
    busy = tracefile.busy_ns(trace, 0, lo, end)
    assert 0 < busy <= end - lo
    # the union never exceeds the plain sum of durations
    assert busy <= sum(e - s for _, s, e in trace["ops"][0])
    gaps = tracefile.idle_gaps(trace, 0, lo, end)
    assert sum(e - s for s, e in gaps) + busy == pytest.approx(end - lo)

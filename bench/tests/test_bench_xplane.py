"""The trace reduction on a synthesised trace: busy and idle share,
per-kernel time and calls, and idle gaps labelled by host annotations."""
from __future__ import annotations

import pytest

import bench_testkit  # noqa: F401  (puts the checkout on the path)

from bench.xplane import Event, breakdown, reduce_events  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS = "XLA Ops"
HOST = "/host:CPU"
MS = 1e6


def _trace():
    return [
        Event(HOST, "python", "bench.window", 0, 100 * MS),
        Event(HOST, "python", "bench.session_run", 0, 60 * MS),
        Event(HOST, "python", "bench.pump", 70 * MS, 20 * MS),
        # chip 0: 10-30 busy (two overlapping ops), 40-50 a kernel,
        # 95-105 runs past the window's end
        Event(D0, OPS, "fusion.1", 10 * MS, 15 * MS),
        Event(D0, OPS, "fusion.2", 20 * MS, 10 * MS),
        Event(D0, OPS, "_per_client_kernel", 40 * MS, 10 * MS),
        Event(D0, OPS, "fusion.3", 95 * MS, 10 * MS),
        Event(D0, "XLA Modules", "jit_run", 10 * MS, 90 * MS),
        # chip 1: 0-50 busy
        Event(D1, OPS, "_agg_kernel", 0, 50 * MS),
    ]


def test_busy_and_window():
    red = reduce_events(_trace(), kernels={"sign_align": ["per_client"],
                                           "masked_agg": ["agg_kernel"]})
    assert red["window_s"] == pytest.approx(0.1)
    # chip 0: 20 + 10 + 5 ms; chip 1: 50 ms -> mean 42.5 ms
    assert red["busy_s"] == pytest.approx(0.0425)
    assert red["chips"] == 2
    assert red["kernels"]["sign_align"] == {"seconds": pytest.approx(0.01),
                                            "calls": 1}
    assert red["kernels"]["masked_agg"]["seconds"] == pytest.approx(0.05)
    assert red["ops"]["fusion.3"] == pytest.approx(0.005)


def test_gaps_are_labelled_by_the_host():
    red = reduce_events(_trace())
    # chip 0 idle: 0-10 and 30-40 inside session_run, 50-95 centred in
    # the pump
    assert [(n, round(s * 1e3, 6)) for n, s in red["gaps"]] == [
        ("bench.pump", 45.0), ("bench.session_run", 10.0),
        ("bench.session_run", 10.0)]
    total = sum(s for _, s in red["gaps"])
    assert total == pytest.approx(0.1 - 0.035)
    b = breakdown(red)
    assert b["device_ops"][0][0] in ("_agg_kernel", "fusion.1")
    assert dict(b["idle_gaps"])["bench.session_run"] == pytest.approx(0.02)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        reduce_events([e for e in _trace() if e.name != "bench.window"])
    with pytest.raises(ValueError):
        reduce_events([e for e in _trace() if e.plane == HOST])


def test_nested_ops_count_their_own_time():
    loop = "%while.7 = (s32[], f32[54,1024]{1,0:T(8,128)}) while(...)"
    body = "%fusion.3 = f32[10,54,1024]{2,1,0:T(8,128)} fusion(...)"
    events = [Event(HOST, "python", "bench.window", 0, 10 * MS),
              Event(D0, OPS, loop, 1 * MS, 8 * MS),
              Event(D0, OPS, body, 2 * MS, 3 * MS),
              Event(D0, OPS, body, 6 * MS, 2 * MS)]
    red = reduce_events(events)
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["ops"]["while.7 (s32[],"] == pytest.approx(0.003)
    assert red["ops"]["fusion.3 f32[10,54,1024]"] == pytest.approx(0.005)

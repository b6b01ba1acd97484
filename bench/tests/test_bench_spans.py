"""The reduction of the program's own marks on a synthesised trace:
device time by named scope, host spans clipped to the window, idle gaps
labelled by the innermost span of either family, and the per-layer
numbers read from them."""
from __future__ import annotations

import pytest

import bench_testkit  # noqa: F401  (puts the checkout on the path)

from bench import xplane  # noqa: E402
from bench.spans import (Event, layer_metrics, load_events,  # noqa: E402
                         nested_scopes, reduce_events, scope_of)

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS = "XLA Ops"
HOST = "/host:CPU"
MS = 1e6
LOOP = "%while.7 = (s32[], f32[54,1024]{1,0:T(8,128)}) while(...)"
BODY = "%fusion.3 = f32[10,54,1024]{2,1,0:T(8,128)} fusion(...)"
PATH = "jit(run_impl)/while/body/{}/dot_general"


def _host(name, start, dur):
    return Event(HOST, "python", name, start * MS, dur * MS)


def _op(name, start, dur, scope="", plane=D0):
    return Event(plane, OPS, name, start * MS, dur * MS, scope)


def _training_trace():
    """A 120 ms window: the benchmark's call (0-110 ms) around one session
    call (0-90) of two dispatches' args, launch, readback and records; the
    device busy 5-35 and 50-80."""
    return [
        _host("bench.window", 0, 120),
        _host("bench.session_run", 0, 110),
        _host("repro.fl.run", 0, 90),
        _host("repro.fl.args", 0, 2), _host("repro.fl.launch", 2, 3),
        _host("repro.fl.readback", 5, 33), _host("repro.fl.records", 38, 6),
        _host("repro.fl.args", 44, 2), _host("repro.fl.launch", 46, 4),
        _host("repro.fl.readback", 50, 32), _host("repro.fl.records", 82, 4),
        _host("repro.fl.session_records", 86, 3),
        # the scan's while holds the round's ops; its own time is the loop
        _op(LOOP, 5, 30, "jit(run_impl)/while"),
        _op(BODY, 6, 10, PATH.format("fl_select")),
        _op(BODY, 16, 14, PATH.format("fl_gather")),
        _op("%fusion.9 = f32[] fusion(...)", 50, 30,
            "jit(run_impl)/while/body/fl_train/vmap(while)/body/mul"),
    ]


def test_scope_of_takes_the_first_fl_component():
    assert scope_of(PATH.format("fl_gather")) == "fl_gather"
    assert scope_of("jit(run_impl)/while/body/fl_train/vmap(fl_x)/add") \
        == "fl_train"
    assert scope_of("jit(run_impl)/while/body/add") == ""
    assert scope_of("") == ""


def test_scopes_sum_self_time_with_the_unscoped_rest():
    red = reduce_events(_training_trace())
    assert red["scopes"] == {"": pytest.approx(0.006),
                             "fl_select": pytest.approx(0.010),
                             "fl_gather": pytest.approx(0.014),
                             "fl_train": pytest.approx(0.030)}
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"])


def test_a_loop_without_a_path_takes_the_scope_it_loops_in():
    """The TPU gives ``while`` ops no ``tf_op``: a loop whose body is one
    phase is that phase's; the round scan, over every phase, is not."""
    ops = [_op(LOOP, 0, 20), _op(LOOP, 1, 8),
           _op(BODY, 2, 2, PATH.format("fl_select")),
           _op(BODY, 5, 3, PATH.format("fl_select")),
           _op(BODY, 10, 5, PATH.format("fl_gather")),
           _op(BODY, 16, 1)]
    assert nested_scopes(ops) == ["", "fl_select", "fl_select", "fl_select",
                                  "fl_gather", ""]
    red = reduce_events([_host("bench.window", 0, 20)] + ops)
    assert red["scopes"] == {"fl_select": pytest.approx(0.008),
                             "fl_gather": pytest.approx(0.005),
                             "": pytest.approx(0.007)}


def test_scopes_are_a_mean_over_chips():
    events = [_host("bench.window", 0, 10),
              _op(BODY, 0, 4, PATH.format("fl_gather")),
              _op(BODY, 0, 2, PATH.format("fl_gather"), plane=D1)]
    red = reduce_events(events)
    assert red["scopes"] == {"fl_gather": pytest.approx(0.003)}
    assert red["busy_s"] == pytest.approx(0.003)


def test_spans_are_clipped_to_the_window():
    events = [_host("bench.window", 10, 50),
              _host("repro.serve.pump", 0, 20),      # 10 ms inside
              _host("repro.serve.pump", 30, 5),
              _host("repro.serve.pump", 55, 10),     # 5 ms inside
              _host("repro.serve.pump", 70, 5),      # after the window
              _host("bench.pump", 30, 5),            # not a program span
              _op(BODY, 30, 1)]
    spans = reduce_events(events)["spans"]
    assert set(spans) == {"repro.serve.pump"}
    assert spans["repro.serve.pump"]["seconds"] == pytest.approx(0.020)
    assert spans["repro.serve.pump"]["count"] == 3


def test_a_gap_is_labelled_by_the_innermost_span_of_either_family():
    red = reduce_events(_training_trace())
    gaps = [(n, round(s * 1e3, 6)) for n, s in red["gaps"]]
    # idle 0-5 (midpoint in the launch), 35-50 (in the records), 80-120
    # (midpoint 100: in the benchmark's call, after the session's)
    assert gaps == [("bench.session_run", 40.0), ("repro.fl.records", 15.0),
                    ("repro.fl.launch", 5.0)]
    # a gap inside a readback, inside the benchmark's call, goes to the
    # readback
    events = _training_trace() + [_op(BODY, 36, 1, PATH.format("fl_x"))]
    events = [e for e in events if e.name != "repro.fl.records"]
    labels = dict((round(s * 1e3, 6), n)
                  for n, s in reduce_events(events)["gaps"])
    assert labels[1.0] == "repro.fl.readback"


def test_a_trace_without_program_marks_reduces_as_xplane_does():
    plain = [Event(*e[:5]) for e in _training_trace()
             if not e.name.startswith("repro.")]
    want = xplane.reduce_events(
        [xplane.Event(*e[:5]) for e in plain],
        kernels={"agg": ["fusion.9"]})
    got = reduce_events(plain, kernels={"agg": ["fusion.9"]})
    assert {k: got[k] for k in want} == want
    assert got["spans"] == {}
    assert got["scopes"] == {"": pytest.approx(got["busy_s"])}


def test_layer_metrics_read_per_round_and_per_flow():
    red = reduce_events(_training_trace())
    m = layer_metrics(red, {"rounds": 10})
    assert m["host_loop_ms"] == pytest.approx((90 - 65) / 10)
    assert m["readback_wait_ms"] == pytest.approx(65 / 10)
    assert m["gather_device_ms"] == pytest.approx(1.4)
    assert m["train_device_ms"] == pytest.approx(3.0)
    assert "eval_device_ms" not in m
    serve = {"spans": {"repro.serve.pad": {"seconds": 0.002, "count": 4},
                       "repro.serve.score": {"seconds": 0.004, "count": 4}}}
    m = layer_metrics(serve, {"answered": 1000})
    assert m == {"pad_us": pytest.approx(2.0), "score_us": pytest.approx(4.0)}
    # the parent program's trace holds none of the marks: nothing is read
    assert layer_metrics({"spans": {}, "scopes": {"": 1.0}},
                         {"rounds": 10}) == {}
    assert layer_metrics({}, {"answered": 10}) == {}


# --- a serialized trace: the op's path sits on its event metadata -----------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf wire bytes of (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, line, events, metas, stats):
    """An XPlane: one line of (metadata id, start ns, duration ns) events;
    ``metas`` {id: (name, [(stat id, str or ref)])}; ``stats`` {id: name}."""
    line = _msg((2, line), (3, 0), *[
        (4, _msg((1, mid), (2, start * 1000), (3, dur * 1000)))
        for mid, start, dur in events])
    meta = [(4, _msg((1, mid), (2, _msg(
        (1, mid), (2, n), *[(5, _msg((1, sid), (5 if isinstance(v, str)
                                                 else 7, v)))
                            for sid, v in st]))))
            for mid, (n, st) in metas.items()]
    stat = [(5, _msg((1, sid), (2, _msg((1, sid), (2, n)))))
            for sid, n in stats.items()]
    return _msg((1, 1), (2, name), (3, line), *meta, *stat)


def test_load_events_reads_each_ops_path_from_its_metadata(tmp_path):
    fusion = "%fusion.1 = f32[8] fusion(...)"
    copy = "%copy.2 = f32[8] copy(...)"
    loop = "%while.3 = (s32[]) while(...)"
    device = _plane(
        "/device:TPU:0", "XLA Ops",
        [(1, 10, 5), (2, 20, 5), (3, 30, 9), (1, 31, 2)],
        {1: (fusion, [(7, PATH.format("fl_gather")), (8, "custom fusion")]),
         2: (copy, [(8, "data formatting")]),
         3: (loop, [(7, 9)])},                  # a path held by reference
        {7: "tf_op", 8: "hlo_category", 9: PATH.format("fl_select")})
    host = _plane("/host:CPU", "python",
                  [(1, 0, 100), (2, 5, 50), (3, 6, 1)],
                  {1: ("bench.window", []), 2: ("repro.fl.run", []),
                   3: ("$profiler.py:1 trace", [])}, {})
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_msg((1, device), (1, host)))
    events = load_events(str(tmp_path))
    got = sorted((e.name, e.start_ns, e.scope) for e in events)
    assert got == [
        (copy, 20.0, ""),
        (fusion, 10.0, PATH.format("fl_gather")),
        (fusion, 31.0, PATH.format("fl_gather")),
        (loop, 30.0, PATH.format("fl_select")),
        ("bench.window", 0.0, ""),
        ("repro.fl.run", 5.0, "")]
    red = reduce_events(events)
    assert red["spans"] == {"repro.fl.run": {"seconds": pytest.approx(5e-8),
                                             "count": 1}}

"""What the program marks in its own trace: host spans and device scopes.

``bench/xplane.py`` reduces a trace to what is seen from outside the
program: device busy time, ops, kernels, and idle gaps labelled by the
benchmark's ``bench.*`` annotations. This module adds what the program
marks itself, on the same events and the same clock:

- host spans named ``repro.*`` (``repro/obs.py``): one per engine call,
  dispatch step or scoring pump;
- the round program's ``jax.named_scope``s (``fl_select`` … ``fl_eval``
  in ``core/megastep.py``), which each device op carries in the path of
  its ``tf_op`` stat (``jit(run_impl)/while/body/fl_gather/...``).

``reduce_events`` returns ``xplane.reduce_events``'s dict with two more
keys, ``scopes`` and ``spans``, and with every idle gap labelled by the
innermost covering annotation of either family. On a trace that holds
neither (a program without them), every other key reads as
``xplane.reduce_events`` gives it. ``layer_metrics`` turns the result
into the per-layer numbers these spans and scopes are for.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional

from bench import xplane

SPAN_PREFIX = "repro."
SCOPE_PREFIX = "fl_"
SCOPE_STAT = "tf_op"
LABEL_PREFIXES = (xplane.ANNOTATION_PREFIX, SPAN_PREFIX)

FL_SCOPES = ("select", "gather", "train", "align", "aggregate", "control",
             "eval")
SERVE_SPANS = ("submit", "take", "pad", "score", "respond")


class Event(NamedTuple):
    """``xplane.Event`` with the op's named-scope path (``""`` where the
    event carries none)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""


def load_events(trace_dir: str) -> List[Event]:
    """Every event ``xplane.load_events`` keeps, plus the host spans named
    ``repro.*``; device ops carry their ``tf_op`` path."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        with open(path, "rb") as f:
            buf = f.read()
        paths_of = op_paths(buf)
        data = ProfileData.from_serialized_xspace(buf)
        for plane in data.planes:
            device = plane.name.startswith(xplane.DEVICE_PREFIX)
            path_of = paths_of.get(plane.name, {})
            for line in plane.lines:
                ops = device and line.name == xplane.OPS_LINE
                for ev in line.events:
                    if not (device or ev.name.startswith(LABEL_PREFIXES)):
                        continue
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns),
                                     path_of.get(ev.name, "") if ops
                                     else ""))
    return out


def op_paths(buf: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane: {op event name: its ``tf_op`` stat}} of a serialized
    XSpace. The stat sits on the op's event metadata, which
    ``jax.profiler.ProfileData`` does not expose, so the planes' metadata
    maps are read straight from the protobuf wire format (XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7)."""
    buf = memoryview(buf)
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _str(buf, v)
            elif f == 4:
                metas.append(_map_value(buf, v))
            elif f == 5:
                sm = dict(_fields(buf, *_map_value(buf, v)))
                stat_names[sm.get(1, 0)] = _str(buf, sm.get(2, (0, 0)))
        if not name.startswith(xplane.DEVICE_PREFIX):
            continue
        want = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        found = out.setdefault(name, {})
        for meta in metas:
            op, value = None, None
            for f, v in _fields(buf, *meta):
                if f == 2:
                    op = _str(buf, v)
                elif f == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in want:
                        value = (_str(buf, st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if op is not None and value is not None:
                found.setdefault(op, value)
    return out


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message in ``buf[lo:hi]``: an int for
    a varint, the (start, end) of the bytes for a length-delimited field;
    fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def _map_value(buf, entry):
    """The value (field 2) of a map entry."""
    return dict(_fields(buf, *entry)).get(2, (0, 0))


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def scope_of(path: str) -> str:
    """The first ``fl_*`` component of a named-scope path, else ``""``."""
    return next((part for part in path.split("/")
                 if part.startswith(SCOPE_PREFIX)), "")


def nested_scopes(ops: List[Event]) -> List[str]:
    """Each op's ``fl_*`` scope, for ops sorted by (start, -duration). An
    op whose path names none takes the one scope that the ops nested in
    it share, if they share one: the TPU gives its ``while`` ops no
    ``tf_op``, and a loop's own time belongs to the phase it loops in."""
    scope = [scope_of(e.scope) for e in ops]
    parent, stack = [-1] * len(ops), []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns \
                <= e.start_ns:
            stack.pop()
        if stack and e.start_ns + e.dur_ns \
                <= ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns:
            parent[i] = stack[-1]
        stack.append(i)
    inner = [set() for _ in ops]
    for i in reversed(range(len(ops))):
        if not scope[i] and len(inner[i]) == 1:
            scope[i] = next(iter(inner[i]))
        if parent[i] >= 0:
            inner[parent[i]] |= {scope[i]} if scope[i] else inner[i]
    return scope


def reduce_events(events: Iterable[Event],
                  kernels: Optional[Dict[str, tuple]] = None) -> dict:
    """``xplane.reduce_events`` plus, in seconds:

    ``scopes`` {``fl_*`` scope (``nested_scopes``) or "": the self time
    of the ops under it, summed over chips and divided by their number,
    as ``busy_s``}; ``spans`` {``repro.*`` name: {"seconds": time inside
    the window, "count": spans that overlap it}}; and ``gaps`` labelled
    by the innermost ``bench.*`` or ``repro.*`` annotation covering each
    gap's midpoint."""
    events = list(events)
    red = xplane.reduce_events(events, kernels)
    w = next(e for e in events if e.name == xplane.WINDOW)
    lo, hi = w.start_ns, w.start_ns + w.dur_ns

    scopes = collections.Counter()
    ops_by_chip = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(xplane.DEVICE_PREFIX) \
                and e.line == xplane.OPS_LINE \
                and e.start_ns + e.dur_ns > lo and e.start_ns < hi:
            ops_by_chip[e.plane].append(e)
    for plane in sorted(ops_by_chip):
        ops = sorted(ops_by_chip[plane], key=lambda e: (e.start_ns,
                                                        -e.dur_ns))
        for name, d in zip(nested_scopes(ops),
                           xplane._self_time(ops, lo, hi)):
            scopes[name] += d / 1e9
    chips = max(len(ops_by_chip), 1)

    spans = {}
    for e in events:
        if e.name.startswith(SPAN_PREFIX) \
                and e.start_ns + e.dur_ns > lo and e.start_ns < hi:
            s = spans.setdefault(e.name, {"seconds": 0.0, "count": 0})
            s["seconds"] += (min(e.start_ns + e.dur_ns, hi)
                             - max(e.start_ns, lo)) / 1e9
            s["count"] += 1

    red["scopes"] = {k: v / chips for k, v in scopes.items()}
    red["spans"] = spans
    red["gaps"] = _label_gaps(events, lo, hi, ops_by_chip)
    return red


def _label_gaps(events, lo, hi, ops_by_chip):
    """Idle gaps of the first chip, each labelled as ``xplane`` labels
    them but by either annotation family, longest first."""
    first = sorted(ops_by_chip)[0]
    busy = xplane._union(xplane._clip(
        [(e.start_ns, e.start_ns + e.dur_ns) for e in ops_by_chip[first]],
        lo, hi))
    notes = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                   for e in events
                   if e.name.startswith(LABEL_PREFIXES)
                   and e.name != xplane.WINDOW)
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    gaps, active, nxt = [], [], 0
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        while nxt < len(notes) and notes[nxt][0] <= mid:
            active.append(notes[nxt])
            nxt += 1
        active = [n for n in active if n[1] >= mid]
        label = min(active, key=lambda n: n[1] - n[0])[2] if active \
            else "host"
        gaps.append((label, (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return gaps


def layer_metrics(red: dict, raw: dict) -> dict:
    """The per-layer numbers the spans and scopes give, from a reduced
    trace and the counts a traffic's window returns (``rounds`` for
    training, ``answered`` for scoring). A number whose span or scope
    the trace lacks is left out.

    Training, per round: ``host_loop_ms`` (``repro.fl.run`` less
    ``repro.fl.readback``), ``readback_wait_ms``, and ``<x>_device_ms``
    for each ``fl_<x>`` scope. Scoring, per flow answered: ``<x>_us``
    for each ``repro.serve.<x>`` span."""
    spans, scopes, out = red.get("spans", {}), red.get("scopes", {}), {}
    if raw.get("rounds"):
        per = 1e3 / raw["rounds"]
        run = spans.get("repro.fl.run")
        wait = spans.get("repro.fl.readback")
        if run and wait:
            out["host_loop_ms"] = (run["seconds"] - wait["seconds"]) * per
        if wait:
            out["readback_wait_ms"] = wait["seconds"] * per
        for x in FL_SCOPES:
            if SCOPE_PREFIX + x in scopes:
                out[f"{x}_device_ms"] = scopes[SCOPE_PREFIX + x] * per
    if raw.get("answered"):
        for x in SERVE_SPANS:
            s = spans.get(f"repro.serve.{x}")
            if s:
                out[f"{x}_us"] = s["seconds"] * 1e6 / raw["answered"]
    return out

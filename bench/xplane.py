"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

The trace is read with ``jax.profiler.ProfileData`` and flattened to
``Event(plane, line, name, start_ns, dur_ns)``; everything after that is
plain arithmetic on those events, so tests can feed synthesised events.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op (a Pallas kernel is one op). The window is the
benchmark's own ``bench.window`` host annotation. Busy time is the union
of op intervals inside the window, per chip, averaged over chips; an
idle gap is labelled with the innermost ``bench.*`` host annotation that
covers its midpoint (``host`` when none does).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_events(trace_dir: str) -> List[Event]:
    """Flatten every ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            keep_all = plane.name.startswith(DEVICE_PREFIX)
            for line in plane.lines:
                for ev in line.events:
                    if keep_all or ev.name.startswith(ANNOTATION_PREFIX):
                        out.append(Event(plane.name, line.name, ev.name,
                                         float(ev.start_ns),
                                         float(ev.duration_ns)))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def short_name(op: str) -> str:
    """``%fusion.12 = f32[10,54,1024]{...} fusion(...)`` -> the op's name
    and result type without layouts: ``fusion.12 f32[10,54,1024]``."""
    head, _, rest = op.partition(" = ")
    rtype = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head.lstrip('%')} {rtype}"[:120].strip()


def _self_time(ops, lo, hi):
    """Each op's time inside [lo, hi] less the time of the ops nested in
    it (a ``while`` or ``conditional`` holds its body's ops)."""
    own = [min(e.start_ns + e.dur_ns, hi) - max(e.start_ns, lo) for e in ops]
    stack = []
    for i, e in enumerate(ops):
        end = e.start_ns + e.dur_ns
        while stack and ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns \
                <= e.start_ns:
            stack.pop()
        if stack and end <= ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns:
            own[stack[-1]] -= own[i]
        stack.append(i)
    return [max(0.0, t) for t in own]


def reduce_events(events: Iterable[Event],
                  kernels: Optional[Dict[str, tuple]] = None) -> dict:
    """Busy/idle, per-op and per-kernel device time of the traced window.

    ``kernels`` maps a kernel's name to the substrings that all appear in
    its op events' names. Returns seconds throughout:
    ``window_s``, ``busy_s`` (mean over chips), ``chips``,
    ``ops`` {short op name: self time, summed over chips; an op nested in
    another is counted in its own entry and not in its parent's},
    ``kernels`` {name: {"seconds", "calls"}} (only kernels found),
    ``gaps`` [(label, seconds)] every idle gap of the first chip,
    longest first."""
    events = list(events)
    windows = [e for e in events if e.name == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    w = windows[0]
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    ops_by_chip = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE:
            ops_by_chip[e.plane].append(e)
    if not ops_by_chip:
        raise ValueError("trace holds no device op events")
    op_time = collections.Counter()
    kern = {}
    busy = []
    for plane in sorted(ops_by_chip):
        ops = sorted((e for e in ops_by_chip[plane]
                      if e.start_ns + e.dur_ns > lo and e.start_ns < hi),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        for e, d in zip(ops, _self_time(ops, lo, hi)):
            op_time[short_name(e.name)] += d / 1e9
        for e in ops:
            for kname, marks in (kernels or {}).items():
                if all(m in e.name for m in marks):
                    k = kern.setdefault(kname, {"seconds": 0.0, "calls": 0})
                    k["seconds"] += (min(e.start_ns + e.dur_ns, hi)
                                     - max(e.start_ns, lo)) / 1e9
                    k["calls"] += 1
        busy.append(_union(_clip([(e.start_ns, e.start_ns + e.dur_ns)
                                  for e in ops], lo, hi)))
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(busy) / 1e9
    notes = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                   for e in events
                   if e.name.startswith(ANNOTATION_PREFIX) and e.name != WINDOW)
    edges = [lo] + [t for s, e in busy[0] for t in (s, e)] + [hi]
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
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s,
            "chips": len(busy), "ops": dict(op_time), "kernels": kern,
            "gaps": gaps}


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time, and idle time summed by what the host was doing."""
    by_label = collections.Counter()
    for label, s in red["gaps"]:
        by_label[label] += s
    return {"device_ops": [[n, s] for n, s in
                           collections.Counter(red["ops"]).most_common(top)],
            "idle_gaps": [[n, s] for n, s in by_label.most_common(top)]}

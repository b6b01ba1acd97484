"""Closed-loop scoring: a SOC re-scores a recorded flow log.

The generator keeps ``outstanding`` flows submitted and not yet answered
in the ``ServeEngine``, topping the queue up before every pump, so every
pump takes a full ``max_batch`` bucket; flows are the seed's log, cycled.
The window counts the flows answered; after it closes the queue is
drained and every id is checked.
"""
from __future__ import annotations

import time

from bench import serving


def setup(ctx):
    state = serving.build(ctx)
    serving.warm(state, [ctx.traffic["max_batch"]])
    return state


def window(state, seconds):
    eng, log = state["engine"], state["flows"]
    tr = state["ctx"].traffic
    annotate = state["ctx"].annotate
    target, n_log = tr["outstanding"], len(log)
    rec = serving.Record(state["salt"])
    submitted = answered = 0
    rid0 = None
    t0 = time.perf_counter()
    while True:
        need = target - (submitted - answered)
        if need > 0:
            with annotate("bench.submit"):
                lo = submitted % n_log
                rows = log[lo:lo + need]
                ids = eng.submit_many(rows)
            if rid0 is None:
                rid0 = ids[0]
            submitted += len(ids)
        with annotate("bench.pump"):
            out = eng.pump()
        answered += len(out)
        rec.add(out)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    rows_per_pump = serving.batch_rows(eng)
    rec.add(eng.drain())
    state.update(rec=rec, rid0=rid0, submitted=submitted)
    return {"attempted": submitted, "answered": answered,
            "seconds": elapsed, "batch_rows": rows_per_pump}


def end_to_end(raw):
    return {"flows_per_s": raw["answered"] / raw["seconds"]}


def check(state):
    return serving.check(state, state["rec"], state["rid0"],
                         state["submitted"])

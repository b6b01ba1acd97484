"""The numbers that decide ``correct``: each a gap between what the timed
path produced and what the plain reference gives, compared with a limit
kept in the traffic file.

Training (``train_numbers``), over the rounds set-up drove through the
window's own call:
  loss0_gap   first round's mean client loss, relative gap
  loss_gap    widest relative gap of any round's mean client loss
  acc_gap     widest absolute gap of the fused eval accuracy
  change_gap  worst leaf: |‖Δ_program‖ − ‖Δ_reference‖| over the larger
              of ‖Δ_reference‖ and the median leaf's, Δ the change of
              the parameters over those rounds
  bytes_gap   relative gap of the bytes sent over all those rounds
  time_gap    widest relative gap of the simulated clock
Leaves whose reference change is under a thousandth of the median
leaf's would move by round-off alone; they are left out of change_gap.

Scoring (``serve_numbers``), over the answers sampled from the seed:
  prob_gap    widest absolute gap of a class probability
  score_gap   widest absolute gap of the anomaly score
"""
from __future__ import annotations

import numpy as np


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-12)


def change_gap(p0, p_prog, p_ref) -> float:
    names = sorted(p0)
    got = np.array([np.linalg.norm(p_prog[n].astype(np.float64) - p0[n])
                    for n in names])
    want = np.array([np.linalg.norm(p_ref[n].astype(np.float64) - p0[n])
                     for n in names])
    med = float(np.median(want))
    keep = want >= 1e-3 * med
    scale = np.maximum(want, med)
    return float(np.max((np.abs(got - want) / scale)[keep]))


def train_numbers(recs_prog, p0, p_prog, recs_ref, p_ref) -> dict:
    pairs = list(zip(recs_prog, recs_ref))
    accs = [abs(a["accuracy"] - b["accuracy"]) for a, b in pairs
            if np.isfinite(b["accuracy"])]
    return {
        "loss0_gap": _rel(recs_prog[0]["loss"], recs_ref[0]["loss"]),
        "loss_gap": max(_rel(a["loss"], b["loss"]) for a, b in pairs),
        "acc_gap": float(max(accs)),
        "change_gap": change_gap(p0, p_prog, p_ref),
        "bytes_gap": _rel(recs_prog[-1]["bytes_sent"],
                          recs_ref[-1]["bytes_sent"]),
        "time_gap": max(_rel(a["sim_time"], b["sim_time"])
                        for a, b in pairs),
    }


def serve_numbers(probs_prog, scores_prog, probs_ref, scores_ref) -> dict:
    if len(probs_ref) == 0:
        return {"prob_gap": float("inf"), "score_gap": float("inf")}
    return {"prob_gap": float(np.max(np.abs(probs_prog - probs_ref))),
            "score_gap": float(np.max(np.abs(scores_prog - scores_ref)))}

"""Normalized result schema shared by BOTH engines.

Every experiment — event-driven simulation (``engine="sim"``) or compiled
SPMD round loop (``engine="spmd"``) — returns one ``ExperimentResult``
holding per-round ``RoundRecord``s with identical field meaning:

  sim_time    simulated end-to-end seconds so far (CommModel units)
  comm_time   cumulative transfer seconds
  idle_time   cumulative barrier-idle seconds (sync semantics)
  bytes_sent  cumulative client->server bytes, 1-bit skip beacons included
  accept_rate fraction of selected clients whose update passed the filter

In the degenerate configuration (equal speeds, zero latency, theta=None,
one local step) the two engines produce identical records (tested in
tests/test_api.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional


@dataclasses.dataclass
class RoundRecord:
    round: int
    sim_time: float
    comm_time: float
    idle_time: float
    bytes_sent: float
    updates_applied: int
    accept_rate: float
    accuracy: float
    loss: float
    expert_rows: int = 0     # rows routed to held experts this round, over
                             # the cohort's training (scanned sim engine;
                             # 0 for models without an expert layer)


ROUND_FIELDS = tuple(f.name for f in dataclasses.fields(RoundRecord))


@dataclasses.dataclass
class ExperimentResult:
    engine: str
    strategy: str                     # registry name, or "<custom>"
    rounds: int
    seed: int
    records: List[RoundRecord]
    cfg: Any = None                   # resolved ArchConfig
    params: Any = None                # final global parameters
    eval_arrays: Any = None           # held-out eval split
    num_clients: int = 0
    param_bytes: int = 0              # one full update's wire size
    wall_time: float = 0.0            # real container seconds

    @property
    def final(self) -> Optional[RoundRecord]:
        return self.records[-1] if self.records else None

    def series(self, field: str) -> List[float]:
        return [getattr(r, field) for r in self.records]

    def to_rows(self):
        """Per-round rows in ROUND_FIELDS order (CSV-friendly)."""
        return [[getattr(r, f) for f in ROUND_FIELDS] for r in self.records]

    @property
    def bytes_baseline(self) -> float:
        """Full-participation upload volume for the same world/rounds."""
        return float(self.num_clients) * self.param_bytes * self.rounds

    def auc_roc(self) -> float:
        """Binary-ised AUC-ROC on the eval split (attack vs Normal).

        Only meaningful for the mlp detector family.
        """
        import jax
        import jax.numpy as jnp

        from repro.models import mlp_detector

        ev = jax.tree.map(jnp.asarray, self.eval_arrays)
        probs = mlp_detector.predict(self.params, ev["x"], self.cfg)
        scores = 1.0 - probs[:, 0]                 # P(not Normal)
        labels = (ev["y"] != 0).astype(jnp.float32)
        return float(mlp_detector.auc_roc(scores, labels))

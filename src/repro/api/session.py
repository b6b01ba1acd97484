"""ExperimentSession — the resumable, streaming experiment driver.

``run_experiment`` is a one-shot call; the paper's statistical apparatus
(repeated runs, Mann-Whitney validation) and any long-lived deployment
need a *driver*: open an experiment, advance it round by round, observe
records as they happen, checkpoint mid-flight, resume bit-identically.

    session = ExperimentSession.open(spec)
    for record in session.stream(spec.rounds):     # RoundRecord stream
        print(record.round, record.accuracy)
    session.checkpoint("run.ckpt")                 # full device state
    ...
    session = ExperimentSession.restore("run.ckpt")
    session.run(10)                                # continues exactly

Resume bit-exactness: a checkpoint serializes the COMPLETE state of the
underlying engine — parameters (arena matrix or pytree), optimizer
state, the device ``ControlState``, every numpy Generator position
(engine, loaders, selector) and the scanned path's PRNG key / absolute
round counter — so a restored session's subsequent records and final
parameters are bit-identical to an uninterrupted run on BOTH engines,
including ``rounds_per_dispatch > 1`` (tests/test_session.py).
Checkpoints do NOT store training data; worlds rebuild deterministically
from the spec's seed. Restoring onto a spec whose trajectory-relevant
fields differ raises :class:`CheckpointMismatchError` naming them. One
nuance under ``eval_every > 1``: each ``run()`` call evaluates its own
final round (so ``result.final`` is always measured), which means a
checkpoint boundary adds one accuracy SAMPLE at the boundary round —
the trajectory and every other record field are unaffected, and with
the default ``eval_every=1`` the record series is bit-identical too.

Callbacks: ``session.add_callback(fn)`` registers ``fn(record)``; return
``False`` (or call ``session.request_stop()``) to stop the run early.
``run(n)`` computes its rounds as one engine batch (fastest; callbacks
observe records afterwards, early-stop takes effect at batch end), while
``stream(n)`` computes dispatch-sized chunks and reacts between chunks.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from repro import faults
from repro.api import runner as runner_mod
from repro.api.result import ExperimentResult, RoundRecord
from repro.api.spec import ExperimentSpec
from repro.checkpoint.io import CheckpointCorruptError
from repro.obs import span

CHECKPOINT_FORMAT = 1

# spec fields that identify a trajectory — a checkpoint refuses to
# restore onto a spec that changes any of these (see _spec_fingerprint).
# `rounds` is NOT one of them: the round budget is a session argument,
# and extending a restored run is exactly what sessions are for.
_FINGERPRINT_DOC = ("engine", "model", "strategy", "schedule", "scenario",
                    "topology", "data", "world", "comm", "seed",
                    "eval_every", "megastep", "rounds_per_dispatch",
                    "fused_eval", "optimizer", "lr_schedule", "eval_fn")


def sidecar_path(ckpt_path: str) -> str:
    """The JSON metadata file written next to every session checkpoint
    (``<ckpt>.meta.json``) — fingerprint, round counter, wall time —
    so consumers (``repro.serve.swap``) can validate provenance and
    staleness WITHOUT unpickling or rebuilding the checkpoint."""
    return ckpt_path + ".meta.json"


def read_sidecar(ckpt_path: str) -> Dict[str, Any]:
    """Load the checkpoint's sidecar metadata dict. Raises
    FileNotFoundError with a pointed message when the checkpoint
    predates sidecar metadata (re-write it with ``checkpoint()``)."""
    path = sidecar_path(ckpt_path)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no sidecar metadata at {path} — the checkpoint predates "
            "sidecar support (or was moved without it); re-write it via "
            "ExperimentSession.checkpoint(), which emits both files")
    with open(path) as f:
        return json.load(f)


def _read_verified_payload(path: str) -> Dict[str, Any]:
    """Read a session checkpoint, verifying its sidecar content digest
    BEFORE unpickling (ISSUE 7): a truncated file, bit-flipped payload,
    stripped sidecar or stale digest raises
    :class:`~repro.checkpoint.io.CheckpointCorruptError` naming the
    offending path — pickle never sees untrusted bytes. Sidecars written
    before digest support (no ``sha256`` field) are accepted as legacy.
    """
    faults.check_active("ckpt_read")
    with open(path, "rb") as f:
        blob = f.read()
    sc = sidecar_path(path)
    if not os.path.exists(sc):
        raise CheckpointCorruptError(
            path, f"missing sidecar {sc!r} — cannot verify integrity "
                  "(re-write via ExperimentSession.checkpoint(), which "
                  "emits both files)")
    try:
        with open(sc) as f:
            meta = json.load(f)
    except Exception as e:
        raise CheckpointCorruptError(
            path, f"unreadable sidecar {sc!r} "
                  f"({type(e).__name__}: {e})") from e
    want = meta.get("sha256")
    if want is not None:
        got = hashlib.sha256(blob).hexdigest()
        if got != want:
            raise CheckpointCorruptError(
                path, f"content digest mismatch (sidecar sha256 {want!r} "
                      f"!= computed {got!r})")
    try:
        payload = pickle.loads(blob)
    except Exception as e:
        raise CheckpointCorruptError(
            path, f"undecodable payload ({type(e).__name__}: {e})") from e
    if not isinstance(payload, dict):
        raise CheckpointCorruptError(
            path, f"payload decodes to {type(payload).__name__}, "
                  "not a checkpoint dict")
    return payload


def latest_good_checkpoint(directory: str,
                           exclude=()) -> Optional[str]:
    """Newest digest-verified session checkpoint in ``directory`` —
    ``*.ckpt`` files ranked by their sidecar's ``written_at`` (newest
    first), skipping ``exclude`` paths and anything whose digest (or
    pickle decode) fails. The recovery source behind
    ``ExperimentSession.restore(..., fallback=True)`` and
    ``ModelSlot.publish_checkpoint(..., fallback=True)``."""
    excl = {os.path.abspath(p) for p in exclude}
    cands = []
    try:
        names = os.listdir(directory or ".")
    except OSError:
        return None
    for name in names:
        if not name.endswith(".ckpt"):
            continue
        p = os.path.join(directory or ".", name)
        if os.path.abspath(p) in excl:
            continue
        try:
            meta = read_sidecar(p)
        except (OSError, ValueError):
            continue
        cands.append((float(meta.get("written_at", 0.0)), p))
    for _t, p in sorted(cands, reverse=True):
        try:
            _read_verified_payload(p)
            return p
        except (CheckpointCorruptError, OSError, faults.InjectedFault):
            continue
    return None


class CheckpointMismatchError(ValueError):
    """Restoring a checkpoint onto a spec describing a different
    trajectory. ``.mismatches`` maps field -> (checkpoint, requested)."""

    def __init__(self, mismatches: Dict[str, tuple]):
        self.mismatches = dict(mismatches)
        detail = "; ".join(f"{k}: checkpoint={a!r} vs spec={b!r}"
                           for k, (a, b) in self.mismatches.items())
        super().__init__(
            "checkpoint does not match the spec it is being restored "
            f"onto — differing fields: {detail}")


def _spec_fingerprint(spec: ExperimentSpec) -> Dict[str, Any]:
    """Plain-data identity of the trajectory a spec describes.

    Callables (data factory, eval_fn, lr_schedule, optimizer objects)
    cannot be content-compared across processes — they contribute a
    stable presence/type marker only, never a repr with a memory
    address (which would spuriously mismatch a faithfully
    reconstructed spec in a new process)."""
    def _marker(obj):
        if obj is None or isinstance(obj, str):
            return obj
        return type(obj).__name__        # stable across processes

    cfg = spec.resolve_model()
    data = dataclasses.asdict(spec.data)
    data["factory"] = spec.data.factory is not None   # presence only
    scenario = spec.resolve_scenario()
    topology = spec.resolve_topology()
    return {
        "engine": spec.engine,
        "model": getattr(cfg, "name", str(spec.model)),
        "strategy": dataclasses.asdict(spec.resolve_strategy()),
        "schedule": dataclasses.asdict(spec.resolve_schedule()),
        "scenario": (None if scenario is None
                     else dataclasses.asdict(scenario)),
        "topology": (None if topology is None
                     else dataclasses.asdict(topology)),
        "data": data,
        "world": dataclasses.asdict(spec.world),
        "comm": dataclasses.asdict(spec.resolve_comm()),
        "seed": spec.seed,
        "eval_every": spec.eval_every,
        "megastep": spec.megastep,
        "rounds_per_dispatch": spec.rounds_per_dispatch,
        "fused_eval": spec.fused_eval,
        "optimizer": _marker(spec.optimizer),
        "lr_schedule": spec.lr_schedule is not None,
        "eval_fn": spec.eval_fn is not None,
    }


class _SimDriver:
    """Session driver for engine='sim' — wraps FederatedSimulation."""

    engine = "sim"

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec
        self.sim = runner_mod.build_simulation(spec)

    def run_rounds(self, n: int, eval_final: bool = True
                   ) -> Iterator[RoundRecord]:
        """Advance n rounds; their records, converted as the session
        consumes them (inside its ``repro.fl.session_records`` span)."""
        prev = len(self.sim.history)
        self.sim.run(n, eval_final=eval_final)
        return map(runner_mod.record_from_metrics, self.sim.history[prev:])

    def state_dict(self) -> dict:
        return self.sim.state_dict()

    def load_state_dict(self, state: dict) -> None:
        self.sim.load_state_dict(state)

    def client_pass_rates(self):
        return self.sim.client_pass_rates()

    def result(self, records, wall_time: float = 0.0) -> ExperimentResult:
        return ExperimentResult(
            engine="sim", strategy=self.spec.strategy_name(),
            rounds=len(records), seed=self.spec.seed,
            records=list(records), cfg=self.sim.cfg,
            params=self.sim.params, eval_arrays=self.sim.eval_arrays,
            num_clients=self.sim.num_clients,
            param_bytes=self.sim.param_bytes, wall_time=wall_time)


class ExperimentSession:
    """Open with :meth:`open` or :meth:`restore` — not the constructor."""

    def __init__(self, spec: ExperimentSpec, driver):
        self.spec = spec
        self._driver = driver
        self.records: List[RoundRecord] = []
        self.callbacks: List[Callable[[RoundRecord], Any]] = []
        self._stopped = False
        self._wall = 0.0

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def open(cls, spec: ExperimentSpec) -> "ExperimentSession":
        spec.validate()
        t0 = time.time()
        if spec.engine == "sim":
            driver = _SimDriver(spec)
        else:
            driver = runner_mod.SpmdDriver(spec)
        session = cls(spec, driver)
        session._wall += time.time() - t0
        return session

    @classmethod
    def restore(cls, path: str,
                spec: Optional[ExperimentSpec] = None, *,
                fallback: bool = False) -> "ExperimentSession":
        """Rebuild a session from :meth:`checkpoint` output and continue
        bit-identically. ``spec`` is only needed when the checkpointed
        spec contained unpicklable callables (eval_fn / data factory /
        lr_schedule); when given, it must describe the SAME trajectory.

        The payload's content digest (sidecar ``sha256``) is verified
        before unpickling — a corrupt artifact raises
        :class:`~repro.checkpoint.io.CheckpointCorruptError` instead of
        pickle garbage. ``fallback=True`` degrades to the newest
        digest-verified ``*.ckpt`` in the same directory
        (:func:`latest_good_checkpoint`); only when none survives does
        the original corruption error surface."""
        try:
            payload = _read_verified_payload(path)
        except (CheckpointCorruptError, OSError, faults.InjectedFault):
            if not fallback:
                raise
            good = latest_good_checkpoint(os.path.dirname(path),
                                          exclude=(path,))
            if good is None:
                raise
            payload = _read_verified_payload(good)
        if payload.get("format") != CHECKPOINT_FORMAT:
            raise ValueError(
                f"unknown session checkpoint format "
                f"{payload.get('format')!r} (expected {CHECKPOINT_FORMAT})")
        if spec is None:
            spec = payload["spec"]
            if spec is None:
                raise ValueError(
                    "this checkpoint does not embed its spec (it held "
                    "unpicklable callables); pass the original spec: "
                    "ExperimentSession.restore(path, spec=...)")
        theirs, ours = payload["fingerprint"], _spec_fingerprint(spec)
        mismatches = {k: (theirs.get(k), ours.get(k))
                      for k in sorted(set(theirs) | set(ours))
                      if theirs.get(k) != ours.get(k)}
        if mismatches:
            raise CheckpointMismatchError(mismatches)
        session = cls.open(spec)
        session._driver.load_state_dict(payload["driver"])
        session.records = [RoundRecord(**r) for r in payload["records"]]
        session._wall = payload["wall_time"]
        return session

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    @property
    def rounds_done(self) -> int:
        return len(self.records)

    @property
    def dispatches(self) -> int:
        """Compiled calls the engine has made so far: one per scanned
        dispatch, step or eval (``FederatedSimulation.dispatches``).
        Only the sim engine counts them."""
        if self.spec.engine != "sim":
            raise AttributeError(
                f"engine={self.spec.engine!r} counts no dispatches")
        return self._driver.sim.dispatches

    @property
    def overlapped_dispatches(self) -> int:
        """Scanned dispatches enqueued while an earlier dispatch's
        metrics were still on their way back: all but the first of each
        ``run`` call; none in ``stream``, which advances one dispatch a
        chunk. Only the sim engine counts them."""
        if self.spec.engine != "sim":
            raise AttributeError(
                f"engine={self.spec.engine!r} counts no dispatches")
        return self._driver.sim.overlapped_dispatches

    @property
    def expert_rows(self) -> int:
        """Rows routed to the experts this chip holds, over every round
        the session ran on the scanned engine (0 for models without an
        expert layer)."""
        if self.spec.engine != "sim":
            raise AttributeError(
                f"engine={self.spec.engine!r} counts no expert rows")
        return self._driver.sim.expert_rows

    @property
    def stopped(self) -> bool:
        return self._stopped

    def request_stop(self) -> None:
        """Ask the session to stop after the current round/chunk —
        callable from inside a callback (the early-stop hook)."""
        self._stopped = True

    def add_callback(self, fn: Callable[[RoundRecord], Any]) -> None:
        """Register ``fn(record)``, fired for every new RoundRecord in
        order; returning ``False`` requests an early stop."""
        self.callbacks.append(fn)

    def _fire(self, records: List[RoundRecord]) -> None:
        for rec in records:
            for cb in self.callbacks:
                if cb(rec) is False:
                    self._stopped = True

    def _remaining(self, rounds: Optional[int]) -> int:
        if rounds is not None:
            return max(0, int(rounds))
        return max(0, self.spec.rounds - self.rounds_done)

    def _advance(self, n: int, eval_final: bool = True
                 ) -> List[RoundRecord]:
        """One engine batch of ``n`` rounds: its records are kept and
        the callbacks fired."""
        with span("repro.fl.run"):
            t0 = time.time()
            new = self._driver.run_rounds(n, eval_final=eval_final)
            with span("repro.fl.session_records"):
                new = list(new)
                self._wall += time.time() - t0
                self.records.extend(new)
                self._fire(new)
        return new

    def run(self, rounds: Optional[int] = None) -> List[RoundRecord]:
        """Advance ``rounds`` more rounds (default: the spec's remaining
        budget) as ONE engine batch and return their records."""
        n = self._remaining(rounds)
        if n == 0 or self._stopped:
            return []
        return self._advance(n)

    def stream(self, rounds: Optional[int] = None) -> Iterator[RoundRecord]:
        """Yield records as they are produced. Chunk size follows the
        engine's dispatch granularity (``rounds_per_dispatch`` on the
        scanned sim path, else 1), so streaming keeps the compiled-path
        amortization; early stop takes effect between chunks. The
        ``eval_every`` cadence is absolute, and only the FINAL round of
        the whole stream gets the extra end-of-run evaluation — the
        accuracy series is identical to a single ``run(n)`` batch."""
        n = self._remaining(rounds)
        chunk = self.spec.rounds_per_dispatch or 1
        done = 0
        while done < n and not self._stopped:
            step = min(chunk, n - done)
            new = self._advance(step, eval_final=(done + step >= n))
            done += len(new)
            yield from new

    def __iter__(self) -> Iterator[RoundRecord]:
        return self.stream()

    def result(self) -> ExperimentResult:
        """The normalized ExperimentResult over everything run so far."""
        return self._driver.result(self.records, wall_time=self._wall)

    def client_pass_rates(self):
        """(num_clients,) per-client θ pass-rate EMAs the server control
        plane has learned so far — the diagnostics surface behind the
        differential harness's byzantine-rejection assert (raises on the
        spmd engine when its control plane is inactive)."""
        return self._driver.client_pass_rates()

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------
    def checkpoint(self, path: str) -> str:
        """Serialize the full session state to ``path`` (atomic write).
        The training data is NOT stored — worlds rebuild from the seed.

        A small JSON sidecar (:func:`sidecar_path`: ``<path>.meta.json``)
        records the spec fingerprint, the absolute round counter and
        wall time, so serving-side consumers (``repro.serve.swap``) can
        reject stale or mismatched models with a clear error without
        unpickling the full checkpoint."""
        fingerprint = _spec_fingerprint(self.spec)
        try:
            pickle.dumps(self.spec)
            spec_blob = self.spec
        except Exception:
            spec_blob = None          # unpicklable callables in the spec
        payload = {
            "format": CHECKPOINT_FORMAT,
            "fingerprint": fingerprint,
            "spec": spec_blob,
            "records": [dataclasses.asdict(r) for r in self.records],
            "wall_time": self._wall,
            "driver": self._driver.state_dict(),
        }
        blob = pickle.dumps(payload)
        # fault-checked BEFORE any byte lands: an injected write error
        # never damages the artifact (or sidecar) already at `path`
        faults.check_active("ckpt_write")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)   # a crash never corrupts the checkpoint
        meta = {
            "format": CHECKPOINT_FORMAT,
            "model": fingerprint["model"],
            "engine": fingerprint["engine"],
            "seed": fingerprint["seed"],
            "rounds_done": self.rounds_done,
            "wall_time": self._wall,
            "written_at": time.time(),
            # content digest of the payload bytes — restore() verifies
            # this before unpickling (CheckpointCorruptError otherwise)
            "sha256": hashlib.sha256(blob).hexdigest(),
            "payload_bytes": len(blob),
            # tuples inside dataclass asdicts become JSON lists; the
            # sidecar is provenance metadata, not an equality oracle —
            # exact fingerprint matching stays in restore()
            "fingerprint": fingerprint,
        }
        mtmp = sidecar_path(path) + ".tmp"
        with open(mtmp, "w") as f:
            json.dump(meta, f, indent=2, default=str)
            f.write("\n")
        os.replace(mtmp, sidecar_path(path))
        return path

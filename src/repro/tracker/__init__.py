"""repro.tracker — lightweight step-scoped metrics layer.

The experiment harness (benchmarks/bench_sweep.py), the launcher
(launch/train.py), and the shared benchmark loops (benchmarks/common.py)
all emit metrics through one interface so every run — paper sweep, CI
smoke, production training — produces the same record stream:

    tracker.log(step, {"loss": 2.31, "grad_norm": 4.2})
    tracker.log_summary({"final_loss": 0.12, "test_acc": 0.94})
    tracker.finish()

Backends are pluggable (modeled on levanter's ``tracker`` +
``callbacks`` split):

  * ``JsonlTracker``   — one JSON object per line; the durable artifact
                         format every ``BENCH_<name>.json`` record is
                         derived from (``read_jsonl`` round-trips it).
  * ``StdoutTracker``  — human-readable progress lines, rate-limited by
                         ``every``.
  * ``MemoryTracker``  — in-memory list of (step, metrics) for tests and
                         for callers that post-process a run (the
                         launcher reads its loss curve back out of one).
  * ``CompositeTracker`` — fan-out to several backends in registration
                         order (deterministic — tests assert it).
  * ``NullTracker``    — the default no-op.

Values may be live jax/numpy device scalars: every backend coerces
through ``scalarize`` at log time, so callers never pay a device sync
just to construct the metrics dict (buffer upstream with
``tracker.callbacks.MetricsBuffer`` to batch the sync).

Host-side only: trackers never appear inside jit. The train step stays
pure (training/step.py) and the loop around it logs.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Tuple

__all__ = [
    "Tracker", "NullTracker", "MemoryTracker", "StdoutTracker",
    "JsonlTracker", "CompositeTracker", "scalarize", "read_jsonl",
]


def scalarize(value: Any) -> Any:
    """Coerce a metric value to a plain JSON-serializable python scalar.
    Accepts python numbers, strings, bools, None, and 0-d jax/numpy
    arrays (anything with ``.item()``); lists/tuples/dicts are coerced
    elementwise.  Non-scalar arrays are rejected loudly — per-step
    metrics are scalars by contract, and silently serializing a (B,S)
    tensor into JSONL is always a bug upstream."""
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, dict):
        return {k: scalarize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [scalarize(v) for v in value]
    if hasattr(value, "ndim") and getattr(value, "ndim") != 0:
        raise TypeError(f"metric value must be a scalar, got array with "
                        f"shape {getattr(value, 'shape', '?')}")
    if hasattr(value, "item"):
        v = value.item()
        # np.float32.item() -> float, np.int32.item() -> int
        return v
    raise TypeError(f"unsupported metric value type {type(value).__name__}")


class Tracker:
    """Metrics backend interface.  ``log`` is step-scoped; ``log_summary``
    records run-level results (final loss, test accuracy, counters);
    ``finish`` flushes/closes.  Subclasses override ``_log`` hooks and
    inherit the scalarization."""

    def log(self, step: int, metrics: Dict[str, Any]) -> None:
        self._log(int(step), {k: scalarize(v) for k, v in metrics.items()})

    def log_summary(self, metrics: Dict[str, Any]) -> None:
        self._log_summary({k: scalarize(v) for k, v in metrics.items()})

    def finish(self) -> None:  # idempotent
        pass

    # -- backend hooks --------------------------------------------------
    def _log(self, step: int, metrics: Dict[str, Any]) -> None:
        raise NotImplementedError

    def _log_summary(self, metrics: Dict[str, Any]) -> None:
        raise NotImplementedError


class NullTracker(Tracker):
    def _log(self, step, metrics):
        pass

    def _log_summary(self, metrics):
        pass


class MemoryTracker(Tracker):
    """Records everything in memory — the test backend, and the cheapest
    way for a caller to read a run's curve back (``.series("loss")``)."""

    def __init__(self) -> None:
        self.steps: List[Tuple[int, Dict[str, Any]]] = []
        self.summary: Dict[str, Any] = {}
        self.finished = False

    def _log(self, step, metrics):
        self.steps.append((step, metrics))

    def _log_summary(self, metrics):
        self.summary.update(metrics)

    def finish(self):
        self.finished = True

    def series(self, key: str) -> List[Any]:
        return [m[key] for _, m in self.steps if key in m]


class StdoutTracker(Tracker):
    """Progress lines on stdout, at most one per ``every`` steps (summary
    always prints).  ``fmt(step, metrics) -> str`` overrides the line."""

    def __init__(self, every: int = 1, prefix: str = "", fmt=None) -> None:
        self.every = max(1, every)
        self.prefix = prefix
        self.fmt = fmt

    def _default_fmt(self, step, metrics):
        body = " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in metrics.items())
        return f"{self.prefix}step {step:5d} {body}"

    def _log(self, step, metrics):
        if step % self.every == 0:
            print((self.fmt or self._default_fmt)(step, metrics))

    def _log_summary(self, metrics):
        body = " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in metrics.items())
        print(f"{self.prefix}summary {body}")


class JsonlTracker(Tracker):
    """One JSON object per line: ``{"step": t, ...metrics}`` for step
    records, ``{"summary": true, ...metrics}`` for run-level records.
    Append mode so a resumed run extends its own file; ``read_jsonl``
    round-trips the stream."""

    def __init__(self, path: str) -> None:
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a")

    def _write(self, obj: Dict[str, Any]) -> None:
        if self._f is None:
            raise ValueError(f"JsonlTracker({self.path!r}) already finished")
        self._f.write(json.dumps(obj, sort_keys=True) + "\n")
        self._f.flush()

    def _log(self, step, metrics):
        self._write({"step": step, **metrics})

    def _log_summary(self, metrics):
        self._write({"summary": True, **metrics})

    def finish(self):
        if self._f is not None:
            self._f.close()
            self._f = None


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a JsonlTracker stream back into its records (blank lines
    skipped), preserving order."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


class CompositeTracker(Tracker):
    """Fan out to several backends, in the order given.  Every backend
    sees every record; ordering is part of the contract (tests pin it) so
    e.g. the MemoryTracker a caller reads back from is always as complete
    as the JSONL file on disk."""

    def __init__(self, trackers) -> None:
        self.trackers = list(trackers)

    def _log(self, step, metrics):
        for t in self.trackers:
            t._log(step, metrics)

    def _log_summary(self, metrics):
        for t in self.trackers:
            t._log_summary(metrics)

    def finish(self):
        for t in self.trackers:
            t.finish()


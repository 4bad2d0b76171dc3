"""Benchmark regression gate: turn BENCH_*.json from write-only
artifacts into an enforced perf trajectory.

``bench.py --check`` / ``bench_host.py --check`` compare the run they
just measured against the recorded history with per-metric tolerances
and exit nonzero on regression; accepted runs are appended, so the
history IS the trajectory and a silent slowdown cannot merge.

Design points:

* the baseline is the **median** of the history for each metric — one
  outlier run must not move the bar the way a best-of or last-run
  baseline would;
* tolerances are per-metric (:class:`MetricSpec`): wall seconds on a
  shared fixture get a wide band, deterministic counters (claim RPCs
  per job, wire bytes) a tight one;
* metrics are addressed by dotted path into the result JSON
  (``"timings.compute_s"``), so the gate reads the same entries the
  bench scripts already print;
* a metric missing from history is skipped (older entries predate it),
  a metric missing from the CURRENT run fails only when the spec says
  ``required`` — new instrumentation must not brick old history.

History lives under a key (default ``"history"``) inside the bench's
JSON file; other top-level keys ("before"/"after"/"smoke" documents)
are preserved across appends.  Everything stdlib; importable by tests
and both bench harnesses.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

#: appended history is capped: the gate wants a recent-epochs baseline,
#: not a forever log (old entries fall off the front).
HISTORY_CAP = 50


@dataclass(frozen=True)
class MetricSpec:
    """One gated metric: dotted *key* into the result entry, relative
    tolerance, and direction ("lower" for times/bytes, "higher" for
    throughput)."""

    key: str
    rel_tol: float = 0.25
    direction: str = "lower"
    required: bool = False

    def __post_init__(self):
        if self.direction not in ("lower", "higher"):
            raise ValueError(f"direction must be lower|higher, "
                             f"got {self.direction!r}")
        if self.rel_tol < 0:
            raise ValueError("rel_tol must be >= 0")


def lookup(entry: Any, key: str) -> Optional[float]:
    """Resolve a dotted path to a number, None when absent/non-numeric."""
    node = entry
    for part in key.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)


def _median(values: List[float]) -> float:
    vs = sorted(values)
    n = len(vs)
    mid = n // 2
    return vs[mid] if n % 2 else (vs[mid - 1] + vs[mid]) / 2.0


def gate(current: Dict[str, Any], history: List[Dict[str, Any]],
         specs: List[MetricSpec]) -> List[str]:
    """Compare *current* against the history medians; returns regression
    descriptions (empty list = pass)."""
    problems: List[str] = []
    for spec in specs:
        cur = lookup(current, spec.key)
        if cur is None:
            if spec.required:
                problems.append(
                    f"{spec.key}: required metric missing from this run")
            continue
        base_vals = [v for v in (lookup(h, spec.key) for h in history)
                     if v is not None]
        if not base_vals:
            continue  # metric newer than all of history: nothing to gate
        base = _median(base_vals)
        if spec.direction == "lower":
            limit = base * (1.0 + spec.rel_tol)
            if cur > limit:
                problems.append(
                    f"{spec.key}: {cur:g} exceeds median {base:g} "
                    f"+{spec.rel_tol:.0%} (limit {limit:g}, "
                    f"n={len(base_vals)})")
        else:
            limit = base * (1.0 - spec.rel_tol)
            if cur < limit:
                problems.append(
                    f"{spec.key}: {cur:g} below median {base:g} "
                    f"-{spec.rel_tol:.0%} (limit {limit:g}, "
                    f"n={len(base_vals)})")
    return problems


def synthetic_entry(history: List[Dict[str, Any]],
                    specs: List[MetricSpec],
                    scale: float = 1.0) -> Dict[str, Any]:
    """A synthetic current-run entry built from the history medians of
    the gated metrics, each multiplied by *scale* (regressed for a
    lower-is-better metric when scale > 1, for a higher-is-better one
    when scale < 1).  The gate's own tier-1 self-check runs on these —
    registry/history-derived numbers, never the test host's wall clock."""
    out: Dict[str, Any] = {"synthetic": True, "scale": scale}
    for spec in specs:
        vals = [v for v in (lookup(h, spec.key) for h in history)
                if v is not None]
        if not vals:
            continue
        node = out
        parts = spec.key.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = _median(vals) * scale
    return out


# -- history file I/O --------------------------------------------------------


def load_history(path: str, key: str = "history",
                 ) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Read a bench JSON file; returns ``(whole_doc, history_list)``.
    Missing file or key yields an empty history (first run seeds it)."""
    data: Dict[str, Any] = {}
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    if isinstance(data, list):  # bare-list legacy form
        data = {key: data}
    history = data.get(key, [])
    if not isinstance(history, list):
        raise ValueError(f"{path}: {key!r} is not a list")
    return data, history


def append_history(path: str, entry: Dict[str, Any],
                   key: str = "history") -> str:
    """Append an ACCEPTED run to the history (capped), preserving the
    file's other top-level keys.  Stamps ``recorded_time`` via the one
    wall-clock mint point."""
    from ..coord import docstore  # lazy: timestamp mint point

    data, history = load_history(path, key)
    entry = dict(entry)
    entry.setdefault("recorded_time", docstore.now())
    history.append(entry)
    data[key] = history[-HISTORY_CAP:]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, default=float)
        f.write("\n")
    return path


def check_and_append(path: str, current: Dict[str, Any],
                     specs: List[MetricSpec], key: str = "history",
                     append: bool = True,
                     match=None) -> List[str]:
    """The bench scripts' one-call flow: gate *current* against the
    file's history; on pass (and *append*) record it.  Returns the
    regression list (empty = accepted).

    *match* (entry -> bool) filters which history entries the gate
    baselines on — e.g. same-platform only, so a TPU run's seconds never
    median into a CPU baseline — while the append still lands in the one
    shared history."""
    _, history = load_history(path, key)
    if match is not None:
        history = [h for h in history if match(h)]
    problems = gate(current, history, specs)
    if not problems and append:
        append_history(path, current, key)
    return problems

"""Benchmark trajectory: ``BENCH_<suite>.json`` recording + diffing.

CI gates answer "did the suite pass?"; they lose the *trajectory* — how
the overhead x-factor, steering gain, utilization, and kernel timings
move across PRs. ``BenchRecorder`` gives every suite in
``benchmarks/run.py`` one write path:

    rec = BenchRecorder("overhead", out_dir="bench_out")
    rec.metric("warm_batched_speedup_x", 9.3, unit="x", gate=(">=", 2.0))
    path = rec.finish()          # -> bench_out/BENCH_overhead.json

The file carries the git commit, a wall-clock timestamp, an environment
fingerprint (python/jax/numpy versions, platform, JAX backend), every
metric with its optional gate threshold and per-metric pass/fail, and a
suite-level verdict. ``bench_diff(old, new)`` compares two recordings
per-metric; a metric with a gate regresses when it moves against the
gate's direction by more than ``rel_tol``, an ungated metric is flagged
as changed only. ``python -m repro.observe bench diff OLD NEW`` is the
CLI (soft-fail annotation in CI; hard gates stay in the suites).
"""

from __future__ import annotations

import importlib.metadata
import json
import os
import platform
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

SCHEMA_VERSION = 1

_OPS = {
    ">=": lambda v, t: v >= t,
    "<=": lambda v, t: v <= t,
    ">": lambda v, t: v > t,
    "<": lambda v, t: v < t,
}


def git_commit(cwd: Optional[str] = None) -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=cwd,
        )
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def env_fingerprint() -> Dict[str, Any]:
    """What the numbers were measured on — enough to explain a diff that
    is really an environment change.

    It never starts a JAX backend: a process that records must leave the
    chip to the process that measures. The backend is named only when
    this process has already started one."""
    fp: Dict[str, Any] = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
        "jax": importlib.metadata.version("jax"),
        "numpy": importlib.metadata.version("numpy"),
    }
    if "jax" in sys.modules:
        import jax
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            fp["jax_backend"] = jax.default_backend()
    return fp


class BenchRecorder:
    """Accumulates one suite's metrics and writes ``BENCH_<name>.json``."""

    def __init__(self, name: str, out_dir: str = ".", meta: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self.out_dir = out_dir
        self.meta = dict(meta or {})
        self.metrics: Dict[str, Dict[str, Any]] = {}
        self.t0 = time.time()
        self.path: Optional[str] = None

    def metric(
        self,
        name: str,
        value: float,
        unit: Optional[str] = None,
        gate: Optional[Tuple[str, float]] = None,
        **extra: Any,
    ) -> None:
        """Record one metric; ``gate=(op, threshold)`` (op in >=, <=, >, <)
        attaches the suite's acceptance bound and per-metric pass/fail."""
        row: Dict[str, Any] = {"value": float(value)}
        if unit:
            row["unit"] = unit
        if gate is not None:
            op, threshold = gate
            if op not in _OPS:
                raise ValueError(f"unknown gate op {op!r} (use one of {sorted(_OPS)})")
            row["gate"] = {"op": op, "threshold": float(threshold)}
            row["passed"] = bool(_OPS[op](float(value), float(threshold)))
        self.metrics[name] = {**row, **extra}

    def finish(self, ok: Optional[bool] = None, error: Optional[str] = None) -> str:
        """Write ``BENCH_<name>.json``; suite verdict = every gated metric
        passed AND the suite itself ran clean (``ok``)."""
        gates_passed = all(m.get("passed", True) for m in self.metrics.values())
        doc = {
            "schema": SCHEMA_VERSION,
            "name": self.name,
            "commit": git_commit(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(self.t0)),
            "duration_s": round(time.time() - self.t0, 3),
            "env": env_fingerprint(),
            "metrics": self.metrics,
            "gates_passed": gates_passed,
            "passed": gates_passed and (ok if ok is not None else True),
        }
        if error:
            doc["error"] = error
        os.makedirs(self.out_dir, exist_ok=True)
        self.path = os.path.join(self.out_dir, f"BENCH_{self.name}.json")
        tmp = f"{self.path}.tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, self.path)
        return self.path


def load_bench(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        doc = json.load(fh)
    if "metrics" not in doc or "name" not in doc:
        raise ValueError(f"{path} is not a BENCH_*.json recording")
    return doc


def bench_diff(old: Dict[str, Any], new: Dict[str, Any], rel_tol: float = 0.05) -> Dict[str, Any]:
    """Per-metric comparison of two recordings of the same suite.

    A *gated* metric regresses when it moves against its gate direction
    by more than ``rel_tol`` (relative) — e.g. a ``>=`` speedup dropping
    5%+ regresses, rising is an improvement. Ungated metrics are
    reported as changed/unchanged only (no direction is knowable).
    """
    out: Dict[str, Any] = {
        "suite": new.get("name"),
        "old_commit": old.get("commit"),
        "new_commit": new.get("commit"),
        "metrics": {},
        "regressions": [],
        "improvements": [],
        "added": sorted(set(new["metrics"]) - set(old["metrics"])),
        "removed": sorted(set(old["metrics"]) - set(new["metrics"])),
    }
    for name in sorted(set(old["metrics"]) & set(new["metrics"])):
        ov = float(old["metrics"][name]["value"])
        nv = float(new["metrics"][name]["value"])
        delta = nv - ov
        rel = delta / abs(ov) if ov else (0.0 if nv == 0 else float("inf"))
        gate = new["metrics"][name].get("gate") or old["metrics"][name].get("gate")
        status = "unchanged"
        if gate is not None:
            higher_better = gate["op"] in (">=", ">")
            worse = rel < -rel_tol if higher_better else rel > rel_tol
            better = rel > rel_tol if higher_better else rel < -rel_tol
            if worse:
                status = "regressed"
                out["regressions"].append(name)
            elif better:
                status = "improved"
                out["improvements"].append(name)
        elif abs(rel) > rel_tol:
            status = "changed"
        row: Dict[str, Any] = {
            "old": ov, "new": nv,
            "delta": delta, "rel": rel, "status": status,
        }
        if gate is not None:
            row["gate"] = gate
            row["passed"] = new["metrics"][name].get("passed")
        out["metrics"][name] = row
    out["ok"] = not out["regressions"]
    return out


def render_diff(diff: Dict[str, Any]) -> str:
    """Human-readable diff table (what the CI annotation prints)."""
    lines = [
        f"bench diff · suite={diff.get('suite')} "
        f"({(diff.get('old_commit') or '?')[:9]} -> {(diff.get('new_commit') or '?')[:9]})"
    ]
    width = max((len(n) for n in diff["metrics"]), default=6)
    for name, row in diff["metrics"].items():
        rel = row["rel"]
        rel_s = f"{rel:+.1%}" if abs(rel) != float("inf") else "new"
        mark = {"regressed": "✗", "improved": "✓", "changed": "~", "unchanged": " "}[row["status"]]
        lines.append(
            f"  {mark} {name:<{width}}  {row['old']:>12.6g} -> {row['new']:>12.6g}"
            f"  ({rel_s}) {row['status']}"
        )
    for name in diff["added"]:
        lines.append(f"  + {name} (new metric)")
    for name in diff["removed"]:
        lines.append(f"  - {name} (removed metric)")
    lines.append(
        "verdict: " + ("OK" if diff["ok"] else f"REGRESSED: {', '.join(diff['regressions'])}")
    )
    return "\n".join(lines)


def diff_paths(old_path: str, new_path: str, rel_tol: float = 0.05) -> Dict[str, Any]:
    return bench_diff(load_bench(old_path), load_bench(new_path), rel_tol=rel_tol)


def match_baselines(old_dir: str, new_dir: str) -> List[Tuple[str, str]]:
    """Pair ``BENCH_*.json`` files by suite name across two directories."""
    def index(d: str) -> Dict[str, str]:
        out = {}
        if os.path.isdir(d):
            for fn in sorted(os.listdir(d)):
                if fn.startswith("BENCH_") and fn.endswith(".json"):
                    out[fn] = os.path.join(d, fn)
        return out

    old_idx, new_idx = index(old_dir), index(new_dir)
    return [(old_idx[k], new_idx[k]) for k in sorted(set(old_idx) & set(new_idx))]


def collect_bench(paths: List[str]) -> List[Dict[str, Any]]:
    """Load every ``BENCH_*.json`` under the given files/directories
    (directories are scanned non-recursively; bad files are skipped —
    a trajectory should aggregate whatever survives, not die on one
    truncated artifact)."""
    docs: List[Dict[str, Any]] = []
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(
                os.path.join(p, fn)
                for fn in sorted(os.listdir(p))
                if fn.startswith("BENCH_") and fn.endswith(".json")
            )
        else:
            files.append(p)
    for path in files:
        try:
            docs.append(load_bench(path))
        except (OSError, ValueError, json.JSONDecodeError):
            continue
    return docs


def build_trajectory(docs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-run recordings into one commit-ordered trajectory.

    Output shape::

        {"schema": 1,
         "suites": {suite: {
             "runs":   [{"commit", "timestamp", "duration_s", "passed"}, ...],
             "series": {metric: [{"commit", "timestamp", "value",
                                  "passed"?}, ...]}}}}

    Runs are ordered by timestamp (recording wall-clock), so appending
    each CI run's artifact yields per-metric series a dashboard can plot
    straight across PRs. Duplicate (commit, timestamp) runs of a suite
    collapse to the last one seen.
    """
    by_suite: Dict[str, Dict[Tuple[Optional[str], Optional[str]], Dict[str, Any]]] = {}
    for doc in docs:
        key = (doc.get("commit"), doc.get("timestamp"))
        by_suite.setdefault(str(doc.get("name")), {})[key] = doc
    suites: Dict[str, Any] = {}
    for suite, runs_by_key in sorted(by_suite.items()):
        runs = sorted(runs_by_key.values(), key=lambda d: (d.get("timestamp") or "", d.get("commit") or ""))
        series: Dict[str, List[Dict[str, Any]]] = {}
        run_rows: List[Dict[str, Any]] = []
        for doc in runs:
            run_rows.append({
                "commit": doc.get("commit"),
                "timestamp": doc.get("timestamp"),
                "duration_s": doc.get("duration_s"),
                "passed": doc.get("passed"),
            })
            for metric, row in sorted(doc.get("metrics", {}).items()):
                point: Dict[str, Any] = {
                    "commit": doc.get("commit"),
                    "timestamp": doc.get("timestamp"),
                    "value": row.get("value"),
                }
                if "passed" in row:
                    point["passed"] = row["passed"]
                series.setdefault(metric, []).append(point)
        suites[suite] = {"runs": run_rows, "series": series}
    return {"schema": SCHEMA_VERSION, "suites": suites}


__all__ = [
    "BenchRecorder",
    "bench_diff",
    "build_trajectory",
    "collect_bench",
    "diff_paths",
    "env_fingerprint",
    "git_commit",
    "load_bench",
    "match_baselines",
    "render_diff",
    "SCHEMA_VERSION",
]

"""Distributed trace assembly: per-task spans, critical-path attribution,
and Chrome/Perfetto trace-event export.

The event log records *points* (lifecycle stages); the paper's Fig. 7
reasons about *intervals* — where a task's wall time actually went. This
module turns grouped task events into six spans per task:

    queue-wait   submitted       -> picked_up       (sat in the request queue)
    pickup       picked_up       -> dispatched      (server routing/batching)
    dispatch     dispatched      -> running         (pool queue + worker handoff)
    run          running         -> completed|failed (the task function)
    result-wait  completed|failed-> result_received (result queue + transfer)
    decision     result_received -> decision_made   (the Thinker reacting)

and attributes each task's *critical span* (its longest interval), so an
overhead report says not just "queue-wait averaged 3 ms" but "queue-wait
dominated 80% of tasks".

Because a ``TraceContext`` rides on every ``Result`` and lands in each
event's ``info``, events emitted by different *processes* (the client's
log and a spawned ``ProcessTaskServer``'s JSONL log) carry the same
``trace_id``; ``merge_jsonl`` interleaves the files by timestamp
(``time.monotonic`` is CLOCK_MONOTONIC: one system-wide clock on Linux)
into one causal trace. ``to_perfetto`` renders tasks, per-site lanes,
and ``kind="profile"`` spans (JAX kernel / surrogate timings) as
Chrome trace-event JSON loadable at https://ui.perfetto.dev.

Span building degrades gracefully: missing stages skip the affected
spans (a killed run still renders), out-of-order pairs are flagged
rather than producing negative durations, and failed tasks end their
``run`` span at ``failed``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

from .events import Event

# (span name, start stage(s), end stage(s)) — first occurrence of any
# alternative counts; completed/failed are alternatives at one position.
SPAN_DEFS: Tuple[Tuple[str, Tuple[str, ...], Tuple[str, ...]], ...] = (
    ("queue-wait", ("submitted",), ("picked_up",)),
    ("pickup", ("picked_up",), ("dispatched",)),
    ("dispatch", ("dispatched",), ("running",)),
    ("run", ("running",), ("completed", "failed")),
    ("result-wait", ("completed", "failed"), ("result_received",)),
    ("decision", ("result_received",), ("decision_made",)),
)

SPAN_NAMES: Tuple[str, ...] = tuple(name for name, _, _ in SPAN_DEFS)


@dataclass
class Span:
    """One interval of a task's life."""

    name: str
    t0: float
    t1: float
    site: str = "main"

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclass
class TaskTrace:
    """All spans of one task (one attempt: retry clones trace separately,
    linked by trace_id/parent_span_id)."""

    task_id: str
    method: Optional[str] = None
    pool: Optional[str] = None
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_span_id: Optional[str] = None
    ok: bool = True
    spans: List[Span] = field(default_factory=list)
    flags: List[str] = field(default_factory=list)  # e.g. out-of-order stages

    @property
    def critical(self) -> Optional[str]:
        """The dominating (longest) span's name."""
        if not self.spans:
            return None
        return max(self.spans, key=lambda s: s.duration).name

    @property
    def total_s(self) -> float:
        return sum(s.duration for s in self.spans)


def _as_events(log_or_events: Any) -> List[Event]:
    if hasattr(log_or_events, "events"):
        return log_or_events.events()
    return list(log_or_events)


# --------------------------------------------------------------------------
# JSONL loading / cross-process merging
# --------------------------------------------------------------------------

_EVENT_FIELDS = ("t", "kind", "stage", "task_id", "method", "topic", "pool", "value", "info")


def load_jsonl(path: str, site: Optional[str] = None) -> List[Event]:
    """Load an ``EventLog`` JSONL sink back into ``Event`` objects.

    ``site`` (default: the file's basename minus ``.jsonl``) is stamped
    into each event's ``info`` so merged traces keep their provenance.
    Truncated final lines (a SIGKILL'd writer) are skipped, not fatal.
    """
    if site is None:
        site = os.path.basename(path)
        if site.endswith(".jsonl"):
            site = site[: -len(".jsonl")]
    events: List[Event] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail line from a killed writer
            kw = {k: row.get(k) for k in _EVENT_FIELDS}
            kw["info"] = dict(kw.get("info") or {})
            kw["info"].setdefault("site", site)
            events.append(Event(**kw))
    return events


def merge_jsonl(paths: Sequence[str]) -> List[Event]:
    """Merge several processes' JSONL logs into one trace, ordered by the
    shared monotonic clock."""
    events: List[Event] = []
    for p in paths:
        events.extend(load_jsonl(p))
    events.sort(key=lambda ev: ev.t)
    return events


# --------------------------------------------------------------------------
# Span building
# --------------------------------------------------------------------------


def build_task_traces(log_or_events: Any) -> List[TaskTrace]:
    """Group task events and cut each task's timeline into spans."""
    by_task: Dict[str, List[Event]] = {}
    for ev in _as_events(log_or_events):
        if ev.kind == "task" and ev.task_id is not None:
            by_task.setdefault(ev.task_id, []).append(ev)

    traces: List[TaskTrace] = []
    for tid, evs in by_task.items():
        tr = TaskTrace(task_id=tid)
        first: Dict[str, Event] = {}
        for ev in evs:
            if ev.stage not in first:
                first[ev.stage] = ev
            if tr.method is None and ev.method:
                tr.method = ev.method
            if tr.trace_id is None and ev.info.get("trace_id"):
                tr.trace_id = ev.info["trace_id"]
                tr.span_id = ev.info.get("span_id")
                tr.parent_span_id = ev.info.get("parent_span_id")
        # Execution-side pool (the executing WorkerPool) wins over the
        # requested pool carried by client-side stages.
        for stage in ("running", "completed", "failed", "submitted"):
            ev = first.get(stage)
            if ev is not None and ev.pool is not None:
                tr.pool = ev.pool
                break
        tr.ok = "failed" not in first or "completed" in first

        for name, starts, ends in SPAN_DEFS:
            a = next((first[s] for s in starts if s in first), None)
            b = next((first[s] for s in ends if s in first), None)
            if a is None or b is None:
                continue  # missing stage: skip the span, keep the rest
            if b.t < a.t:
                tr.flags.append(f"out-of-order:{name}")
                continue
            tr.spans.append(
                Span(name=name, t0=a.t, t1=b.t, site=str(a.info.get("site", "main")))
            )
        traces.append(tr)
    traces.sort(key=lambda t: (t.spans[0].t0 if t.spans else 0.0))
    return traces


def span_summary(traces: Iterable[TaskTrace]) -> Dict[str, Any]:
    """Fig.-7-style overhead breakdown with critical-path attribution:
    per-span count/mean/total seconds, the share of total traced time,
    and how many tasks each span dominated."""
    agg: Dict[str, Dict[str, float]] = {
        name: {"count": 0, "total_s": 0.0} for name in SPAN_NAMES
    }
    critical: Dict[str, int] = {}
    n_tasks = 0
    flagged = 0
    for tr in traces:
        n_tasks += 1
        if tr.flags:
            flagged += 1
        for sp in tr.spans:
            agg[sp.name]["count"] += 1
            agg[sp.name]["total_s"] += sp.duration
        crit = tr.critical
        if crit is not None:
            critical[crit] = critical.get(crit, 0) + 1
    grand = sum(a["total_s"] for a in agg.values()) or 1.0
    spans = {
        name: {
            "count": int(a["count"]),
            "mean_s": (a["total_s"] / a["count"]) if a["count"] else 0.0,
            "total_s": a["total_s"],
            "frac": a["total_s"] / grand,
        }
        for name, a in agg.items()
        if a["count"]
    }
    return {
        "tasks": n_tasks,
        "flagged": flagged,
        "spans": spans,
        "critical_path": dict(sorted(critical.items(), key=lambda kv: -kv[1])),
    }


# --------------------------------------------------------------------------
# Perfetto / Chrome trace-event export
# --------------------------------------------------------------------------


def to_perfetto(log_or_events: Any) -> Dict[str, Any]:
    """Render the event log as Chrome trace-event JSON (Perfetto-loadable).

    Layout: one *process* per site (the client's log, each spawned
    server's log), one *thread* lane per span type, "X" complete events
    in microseconds. ``kind="profile"`` events (kernel/surrogate
    timings) get their own process with a lane per profiled name.
    """
    events = _as_events(log_or_events)
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(ev.t for ev in events)

    trace_events: List[Dict[str, Any]] = []
    pids: Dict[str, int] = {}
    tids: Dict[Tuple[str, str], int] = {}

    def pid_for(site: str) -> int:
        if site not in pids:
            pids[site] = len(pids) + 1
            trace_events.append(
                {"ph": "M", "name": "process_name", "pid": pids[site], "tid": 0,
                 "args": {"name": site}}
            )
        return pids[site]

    def tid_for(site: str, lane: str) -> int:
        key = (site, lane)
        if key not in tids:
            tids[key] = len(tids) + 1
            trace_events.append(
                {"ph": "M", "name": "thread_name", "pid": pid_for(site),
                 "tid": tids[key], "args": {"name": lane}}
            )
        return tids[key]

    for tr in build_task_traces(events):
        for sp in tr.spans:
            args: Dict[str, Any] = {"task_id": tr.task_id}
            if tr.pool:
                args["pool"] = tr.pool
            if tr.trace_id:
                args["trace_id"] = tr.trace_id
                args["span_id"] = tr.span_id
            if tr.parent_span_id:
                args["parent_span_id"] = tr.parent_span_id
            if not tr.ok:
                args["failed"] = True
            trace_events.append(
                {
                    "name": f"{tr.method or '?'}:{sp.name}",
                    "cat": "task",
                    "ph": "X",
                    "ts": (sp.t0 - t0) * 1e6,
                    "dur": max(sp.duration, 0.0) * 1e6,
                    "pid": pid_for(sp.site),
                    "tid": tid_for(sp.site, sp.name),
                    "args": args,
                }
            )

    for ev in events:
        if ev.kind != "profile" or ev.value is None:
            continue
        site = str(ev.info.get("site", "main"))
        args = {k: v for k, v in ev.info.items() if k != "site"}
        trace_events.append(
            {
                "name": ev.stage,
                "cat": "profile",
                "ph": "X",
                "ts": (ev.t - t0) * 1e6,
                "dur": max(float(ev.value), 0.0) * 1e6,
                "pid": pid_for(f"profile:{site}"),
                "tid": tid_for(f"profile:{site}", ev.stage),
                "args": args,
            }
        )

    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def export_perfetto(
    inputs: Union[str, Sequence[str]], out_path: str
) -> Dict[str, Any]:
    """Merge one or more JSONL event logs and write Perfetto JSON."""
    paths = [inputs] if isinstance(inputs, str) else list(inputs)
    events = merge_jsonl(paths)
    doc = to_perfetto(events)
    with open(out_path, "w") as fh:
        json.dump(doc, fh)
    return doc


__all__ = [
    "SPAN_DEFS",
    "SPAN_NAMES",
    "Span",
    "TaskTrace",
    "build_task_traces",
    "span_summary",
    "load_jsonl",
    "merge_jsonl",
    "to_perfetto",
    "export_perfetto",
]

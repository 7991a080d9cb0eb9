"""repro.observe — workflow telemetry + adaptive resource reallocation.

The paper's first scaling pillar is *steering strategies that maximize
node utilization*; its evaluation rests on per-task lifecycle traces.
This subsystem provides both: a structured event log every core
component emits into, streaming metrics over it, and an adaptive
reallocator that closes the loop by moving slots toward demand.

Quick wiring::

    from repro.core import LocalColmenaQueues, TaskServer
    from repro.observe import EventLog, MetricsAggregator, build_report

    log = EventLog(jsonl_path="run.jsonl")       # optional persistent sink
    queues = LocalColmenaQueues(event_log=log)   # client-side stages
    server = TaskServer(queues, methods).start() # server/worker stages
    ... run a thinker ...
    print(render_text(build_report(log, total_slots=8)))

Event schema
------------
Every record is an ``Event`` (see ``events.py``), JSONL-serialized when a
sink path is given. Fields:

===========  ============================================================
``t``        ``time.monotonic()`` seconds at emission (``t_rel`` in the
             JSONL sink is relative to log creation)
``kind``     ``task`` (lifecycle stage), ``gauge`` (named scalar sample,
             e.g. ``slots``, ``workers`` — the elastic fleet size — or
             ``batch_occupancy``), ``cache`` (warm-worker cache
             ``hit``/``miss``), ``realloc`` (steering-slot move),
             ``pool_resize`` (elastic worker-fleet ``grow``/``shrink``;
             value = new size, info carries old/new/reason), or
             ``surrogate`` (model lifecycle: ``retrain`` with
             value=rmse, ``rerank`` with value=acquisition regret), or
             ``profile`` (a timed span: ``t`` = start, ``value`` = wall
             seconds, ``stage`` = span name, ``info["device_s"]`` =
             post-``block_until_ready`` device time — emitted by
             ``EventLog.profile`` around kernel / ensemble calls),
             ``alert`` (an SLO/anomaly transition: stage ``pending``/
             ``firing``/``resolved``, ``info["name"]`` the objective,
             ``info["severity"]`` ``page``/``ticket``/``advisory``), or
             ``remediation`` (an auto-remediation attempt: ``stage`` =
             handler label, ``info`` carries the alert name and ``ok``).
             The kind set is OPEN: consumers must tolerate (count, not
             crash on) kinds they do not model — see
             ``MetricsAggregator.unknown_kinds``
``stage``    lifecycle stage for tasks — in causal order: ``submitted``,
             ``queued``, ``picked_up``, ``dispatched``, ``running``,
             ``completed``/``failed``, ``result_received``,
             ``decision_made``; plus out-of-band ``retried`` /
             ``speculated`` / ``reallocated``. For gauges: the gauge
             name (e.g. ``slots``).
``task_id``  the ``Result.task_id`` (``task`` events only; speculative
             twins share the original's id, retry clones get a fresh id
             linked via ``info["origin"]``)
``method``   task-server method name
``topic``    result-queue topic
``pool``     requested pool on client-side stages; the *executing*
             WorkerPool name on ``running``/``completed``/``failed``
``value``    gauge value / slots moved
``info``     free-form extras (``worker_id``, failure kind, ``src``/
             ``dst`` of a reallocation, ...)
===========  ============================================================

Emission points: ``ColmenaQueues.send_inputs`` (submitted, queued),
``ColmenaQueues.get_task`` (picked_up), ``WorkerPool.submit``
(dispatched), the worker loop (running, completed, failed),
``TaskServer`` (retried, speculated), ``ColmenaQueues.get_result``
(result_received), ``BaseThinker`` result processors (decision_made),
``ResourceCounter`` (``slots`` gauges on allocation changes).

Cross-process note: ``event_log`` is process-local (it is dropped on
pickling). With ``PipeColmenaQueues`` each side records its own stages —
a spawned ``ProcessTaskServer`` child opens its own JSONL sink
(``ObserveSpec.resolved_server_jsonl``) — and a ``TraceContext`` minted
at ``send_inputs`` rides on the ``Result`` across the boundary, so
``trace.merge_jsonl`` reassembles the sinks into one causal trace
(``python -m repro.observe trace a.jsonl b.jsonl -o trace.json``).
"""

from .anomaly import AnomalyDetector, AnomalySpec
from .bench import (
    BenchRecorder,
    bench_diff,
    build_trajectory,
    env_fingerprint,
    load_bench,
    render_diff,
)
from .events import (
    AUX_STAGES,
    Event,
    EventLog,
    STAGE_ORDER,
    lifecycle_gaps,
    lifecycle_order_violations,
)
from .export import ExportSpec, MetricsExporter
from .ops import OpsServer
from .metrics import (
    BatchStats,
    CacheStats,
    LatencyHistogram,
    MetricsAggregator,
    PoolStats,
)
from .reallocator import (
    AdaptiveReallocator,
    ElasticPolicy,
    ElasticScaler,
    EMABacklogPolicy,
    GreedyBacklogPolicy,
    Move,
    PoolView,
    ReallocationPolicy,
    ReallocatorMixin,
)
from .report import build_report, dump_json, render_text
from .slo import SLOEngine, SLOObjective, SLOSpec, default_objectives
from .synthetic import PoolWorkloadThinker, run_bursty, run_pool_workload, run_two_pool
from .trace import (
    Span,
    TaskTrace,
    build_task_traces,
    export_perfetto,
    load_jsonl,
    merge_jsonl,
    span_summary,
    to_perfetto,
)

__all__ = [
    "AdaptiveReallocator",
    "AnomalyDetector",
    "AnomalySpec",
    "AUX_STAGES",
    "BatchStats",
    "bench_diff",
    "BenchRecorder",
    "build_report",
    "build_task_traces",
    "build_trajectory",
    "default_objectives",
    "CacheStats",
    "dump_json",
    "env_fingerprint",
    "export_perfetto",
    "ExportSpec",
    "load_bench",
    "merge_jsonl",
    "MetricsExporter",
    "OpsServer",
    "render_diff",
    "Span",
    "span_summary",
    "TaskTrace",
    "to_perfetto",
    "ElasticPolicy",
    "ElasticScaler",
    "EMABacklogPolicy",
    "Event",
    "EventLog",
    "GreedyBacklogPolicy",
    "LatencyHistogram",
    "lifecycle_gaps",
    "lifecycle_order_violations",
    "load_jsonl",
    "MetricsAggregator",
    "Move",
    "PoolStats",
    "PoolView",
    "PoolWorkloadThinker",
    "ReallocationPolicy",
    "ReallocatorMixin",
    "render_text",
    "run_bursty",
    "run_pool_workload",
    "run_two_pool",
    "SLOEngine",
    "SLOObjective",
    "SLOSpec",
    "STAGE_ORDER",
]

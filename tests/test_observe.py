"""Tests for repro.observe: event lifecycle completeness/ordering (incl.
under concurrent task servers), metrics aggregation on a synthetic trace,
reallocator policies, and the static-vs-adaptive acceptance comparison."""

import json
import threading
import time

import pytest

from repro.core import (
    LocalColmenaQueues,
    ResourceRequest,
    Result,
    ResourceCounter,
    TaskServer,
    WorkerPool,
)
from repro.observe import (
    AdaptiveReallocator,
    EMABacklogPolicy,
    Event,
    EventLog,
    GreedyBacklogPolicy,
    MetricsAggregator,
    PoolView,
    build_report,
    lifecycle_gaps,
    lifecycle_order_violations,
    render_text,
    run_two_pool,
)

REQUIRED = ("submitted", "queued", "picked_up", "dispatched", "running",
            "completed", "result_received")


def _run_tasks(log, n_tasks=12, n_servers=1, pools=("alpha", "beta")):
    """Push n_tasks through n_servers sharing one queue; drain results."""
    q = LocalColmenaQueues(event_log=log)
    servers = [
        TaskServer(
            q, {"work": lambda x: x * 2},
            pools={p: WorkerPool(p, 2) for p in (*pools, "default")},
        ).start()
        for _ in range(n_servers)
    ]
    for i in range(n_tasks):
        q.send_inputs(i, method="work",
                      resources=ResourceRequest(pool=pools[i % len(pools)]))
    results = [q.get_result(timeout=30) for _ in range(n_tasks)]
    for s in servers:
        s.stop()
    return q, results


class TestEventLifecycle:
    def test_full_lifecycle_recorded(self):
        log = EventLog()
        _, results = _run_tasks(log, n_tasks=10)
        assert all(r is not None and r.success for r in results)
        by_task = log.by_task()
        assert len(by_task) == 10
        for tid, evs in by_task.items():
            stages = [e.stage for e in evs]
            for s in REQUIRED:
                assert s in stages, f"{tid} missing {s}: {stages}"
        assert lifecycle_gaps(log) == {}
        assert lifecycle_order_violations(log) == []

    def test_lifecycle_under_concurrent_servers(self):
        log = EventLog()
        _, results = _run_tasks(log, n_tasks=24, n_servers=3)
        assert all(r is not None and r.success for r in results)
        assert lifecycle_gaps(log) == {}
        assert lifecycle_order_violations(log) == []
        # Each task is picked up by exactly one of the competing servers.
        counts = {}
        for ev in log.events():
            if ev.kind == "task" and ev.stage == "picked_up":
                counts[ev.task_id] = counts.get(ev.task_id, 0) + 1
        assert len(counts) == 24
        assert set(counts.values()) == {1}

    def test_failed_task_lifecycle(self):
        log = EventLog()
        q = LocalColmenaQueues(event_log=log)
        def boom(x):
            raise ValueError("nope")
        server = TaskServer(q, {"boom": boom}, n_workers=1).start()
        q.send_inputs(1, method="boom")
        r = q.get_result(timeout=30)
        server.stop()
        assert r is not None and not r.success
        stages = {e.stage for e in log.by_task()[r.task_id]}
        assert "failed" in stages and "completed" not in stages
        assert lifecycle_gaps(log) == {}

    def test_ring_buffer_capacity_and_jsonl_sink(self, tmp_path):
        path = tmp_path / "events.jsonl"
        log = EventLog(capacity=4, jsonl_path=str(path))
        for i in range(10):
            log.gauge("slots", i, pool="p")
        log.close()
        assert len(log) == 4  # ring keeps only the most recent
        assert [e.value for e in log.events()] == [6.0, 7.0, 8.0, 9.0]
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        assert len(rows) == 10  # the sink keeps everything
        assert rows[0]["stage"] == "slots" and rows[0]["kind"] == "gauge"
        assert "t_rel" in rows[0]

    def test_subscribe_replays_buffered_events(self):
        log = EventLog()
        log.gauge("slots", 3, pool="p")
        seen = []
        log.subscribe(seen.append, replay=True)
        log.gauge("slots", 4, pool="p")
        assert [e.value for e in seen] == [3.0, 4.0]


def _task(tid, stage, t, pool="sim", method="work", **info):
    return Event(t=t, kind="task", stage=stage, task_id=tid,
                 method=method, topic="default", pool=pool, info=info)


class TestMetricsAggregation:
    def test_synthetic_trace_aggregation(self):
        agg = MetricsAggregator()
        # Two tasks on pool sim: compute 1.0s and 3.0s; one on ml: 2.0s.
        trace = []
        for tid, pool, t0, dur in (("a", "sim", 0.0, 1.0),
                                   ("b", "sim", 0.5, 3.0),
                                   ("c", "ml", 1.0, 2.0)):
            trace += [
                _task(tid, "submitted", t0, pool=pool),
                _task(tid, "queued", t0 + 0.01, pool=pool),
                _task(tid, "picked_up", t0 + 0.02, pool=pool),
                _task(tid, "dispatched", t0 + 0.1, pool=pool),
                _task(tid, "running", t0 + 0.2, pool=pool),
                _task(tid, "completed", t0 + 0.2 + dur, pool=pool),
                _task(tid, "result_received", t0 + 0.3 + dur, pool=pool),
            ]
        for ev in sorted(trace, key=lambda e: e.t):
            agg.observe(ev)

        pools = agg.pool_stats()
        assert pools["sim"].completed == 2
        assert pools["ml"].completed == 1
        assert pools["sim"].busy_seconds == pytest.approx(4.0)
        assert pools["ml"].busy_seconds == pytest.approx(2.0)
        assert pools["sim"].backlog == 0 and pools["sim"].running == 0

        methods = agg.method_stats()
        assert methods["work"]["count"] == 3
        assert methods["work"]["mean_s"] == pytest.approx(2.0)

        over = agg.overhead()
        assert over["queue"]["mean_s"] == pytest.approx(0.1)
        assert over["dispatch"]["mean_s"] == pytest.approx(0.1)
        assert over["compute"]["mean_s"] == pytest.approx(2.0)
        assert over["result"]["mean_s"] == pytest.approx(0.1)

        # makespan: first submit (t=0.0) to last result (b at 0.5+0.3+3.0)
        assert agg.makespan() == pytest.approx(3.8)
        util = agg.utilization(slots_by_pool={"sim": 2, "ml": 2})
        assert util["sim"] == pytest.approx(4.0 / (2 * 3.8))
        assert util["total"] == pytest.approx(6.0 / (4 * 3.8))

    def test_backlog_tracks_submitted_not_running(self):
        agg = MetricsAggregator()
        agg.observe(_task("a", "submitted", 0.0))
        agg.observe(_task("b", "submitted", 0.1))
        assert agg.backlog("sim") == 2
        agg.observe(_task("a", "running", 0.2, info={}))
        assert agg.backlog("sim") == 1

    def test_speculative_twin_not_double_counted(self):
        agg = MetricsAggregator()
        agg.observe(_task("a", "submitted", 0.0))
        agg.observe(_task("a", "running", 1.0, worker_id=0))
        agg.observe(_task("a", "speculated", 5.0))
        agg.observe(_task("a", "running", 5.1, worker_id=1))      # twin
        agg.observe(_task("a", "completed", 6.1, worker_id=1))    # twin wins
        agg.observe(_task("a", "result_received", 6.2))
        agg.observe(_task("a", "decision_made", 6.3))
        agg.observe(_task("a", "completed", 7.0, worker_id=0))    # late loser
        st = agg.pool_stats()["sim"]
        assert st.completed == 1           # one task, not one per copy
        assert st.running == 0             # both copies retired
        # busy time covers BOTH copies' real worker occupancy
        assert st.busy_seconds == pytest.approx((6.1 - 5.1) + (7.0 - 1.0))
        assert agg.method_stats()["work"]["count"] == 1
        # transient per-task state fully dropped (no leak from the
        # decision_made / late-loser events arriving after result_received)
        assert agg._marks == {} and agg._run_start == {}

    def test_capacity_integral_from_slot_gauges(self):
        agg = MetricsAggregator()
        agg.observe(Event(t=0.0, kind="gauge", stage="slots", pool="sim", value=4))
        agg.observe(Event(t=10.0, kind="gauge", stage="slots", pool="sim", value=2))
        agg.observe(_task("x", "submitted", 20.0))
        # 4 slots for 10 s + 2 slots for 10 s = 60 slot-seconds
        assert agg.capacity_slot_seconds("sim", until=20.0) == pytest.approx(60.0)


class TestReallocator:
    def test_greedy_shifts_toward_backlogged_pool(self):
        rec = ResourceCounter(4, pools=["a", "b"])  # all 4 slots in "a"
        backlog = {"a": 0, "b": 5}
        r = AdaptiveReallocator(rec, pools=["a", "b"],
                                policy=GreedyBacklogPolicy(),
                                backlog=lambda p: backlog[p])
        assert r.step() is True
        assert rec.allocation("b") == 4  # all idle slots migrate at once
        assert rec.allocation("a") == 0
        assert r.step() is False  # nothing left to move

    def test_min_slots_floor_respected(self):
        rec = ResourceCounter(4, pools=["a", "b"])
        r = AdaptiveReallocator(rec, pools=["a", "b"],
                                policy=GreedyBacklogPolicy(),
                                backlog=lambda p: 9 if p == "b" else 0,
                                min_slots={"a": 3})
        r.step()
        assert rec.allocation("a") == 3
        assert rec.allocation("b") == 1

    def test_busy_slots_never_move(self):
        rec = ResourceCounter(2, pools=["a", "b"])
        assert rec.acquire("a", 2, timeout=1)  # both slots busy
        r = AdaptiveReallocator(rec, pools=["a", "b"],
                                policy=GreedyBacklogPolicy(),
                                backlog=lambda p: 5 if p == "b" else 0,
                                acquire_timeout=0.01)
        assert r.step() is False
        assert rec.allocation("a") == 2

    def test_ema_policy_has_hysteresis(self):
        policy = EMABacklogPolicy(alpha=1.0, hysteresis=1.0)
        views = [PoolView("a", allocation=2, free=1, backlog=0),
                 PoolView("b", allocation=2, free=0, backlog=1)]
        assert policy.decide(views) is None  # gap too small: no thrash
        views[1] = PoolView("b", allocation=2, free=0, backlog=8)
        mv = policy.decide(views)
        assert mv is not None and mv.src == "a" and mv.dst == "b" and mv.n == 1

    def test_resource_counter_allocation_tracking(self):
        rec = ResourceCounter(6, pools=["x", "y"])
        assert rec.allocations() == {"x": 6, "y": 0}
        rec.reallocate("x", "y", 2)
        assert rec.allocations() == {"x": 4, "y": 2}
        assert rec.acquire("y", 1, timeout=1)
        assert rec.allocation("y") == 2  # acquire does not change allocation
        rec.grow("y", 3)
        assert rec.allocations() == {"x": 4, "y": 5}
        assert rec.shrink("x", 4, timeout=1)
        assert rec.allocations() == {"x": 0, "y": 5}


class TestAdaptiveBeatsStatic:
    """The acceptance comparison: on the imbalanced two-pool workload the
    AdaptiveReallocator must reach at least the static split's
    utilization, with a complete lifecycle trace for every task."""

    @pytest.fixture(scope="class")
    def runs(self):
        static, _, _ = run_two_pool(
            n_slots=6, n_sim=30, n_ml=5, task_s=0.03, adaptive=False)
        adaptive, log, thinker = run_two_pool(
            n_slots=6, n_sim=30, n_ml=5, task_s=0.03, adaptive=True)
        return static, adaptive, log, thinker

    def test_all_tasks_complete(self, runs):
        static, adaptive, _, thinker = runs
        assert static["pools"]["sim"]["completed"] == 30
        assert static["pools"]["ml"]["completed"] == 5
        assert adaptive["pools"]["sim"]["completed"] == 30
        assert adaptive["pools"]["ml"]["completed"] == 5
        assert len(thinker.results) == 35

    def test_adaptive_utilization_at_least_static(self, runs):
        static, adaptive, _, _ = runs
        # The static split strands the ml slots once ml work drains
        # (~half the slots idle for most of the run), so adaptive wins by
        # a wide margin — the >= assertion is robust to scheduling noise.
        assert adaptive["utilization"]["total"] >= static["utilization"]["total"]

    def test_reallocation_happened(self, runs):
        _, adaptive, _, thinker = runs
        assert thinker.reallocator is not None
        assert len(thinker.reallocator.moves) >= 1
        assert adaptive["reallocations"]  # recorded in the event log too
        assert all(m["dst"] == "sim" for m in adaptive["reallocations"])

    def test_event_log_has_every_lifecycle_stage(self, runs):
        _, _, log, _ = runs
        assert lifecycle_gaps(log) == {}
        assert lifecycle_order_violations(log) == []
        by_task = log.by_task()
        assert len(by_task) == 35
        for tid, evs in by_task.items():
            stages = {e.stage for e in evs}
            missing = [s for s in REQUIRED if s not in stages]
            assert not missing, f"{tid} missing {missing}"


class TestReportRendering:
    def test_build_and_render(self):
        log = EventLog()
        _run_tasks(log, n_tasks=6)
        report = build_report(log, total_slots=4)
        assert report["lifecycle"]["complete"]
        assert report["stage_counts"]["completed"] == 6
        assert 0 < report["utilization"]["total"] <= 1.0
        text = render_text(report)
        assert "lifecycle:       complete & ordered" in text
        assert "overhead breakdown" in text
        assert "task spans" in text  # Fig.-7-style span breakdown folded in


def _lifecycle(tid, t0=0.0, pool="sim", method="work", fail=False, **info):
    """A complete synthetic lifecycle for one task, 0.1 s per hop."""
    stages = ["submitted", "queued", "picked_up", "dispatched", "running",
              "failed" if fail else "completed", "result_received",
              "decision_made"]
    return [_task(tid, s, t0 + 0.1 * i, pool=pool, method=method, **info)
            for i, s in enumerate(stages)]


class TestSpanBuilder:
    def test_full_lifecycle_yields_all_six_spans(self):
        from repro.observe import build_task_traces, span_summary

        traces = build_task_traces(_lifecycle("a"))
        assert len(traces) == 1
        tr = traces[0]
        assert [s.name for s in tr.spans] == [
            "queue-wait", "pickup", "dispatch", "run",
            "result-wait", "decision"]
        # submitted -> picked_up is two hops; every other span is one.
        assert tr.critical == "queue-wait"
        assert tr.ok and not tr.flags
        summary = span_summary(traces)
        assert summary["tasks"] == 1 and summary["flagged"] == 0
        assert summary["critical_path"] == {"queue-wait": 1}
        assert summary["spans"]["run"]["mean_s"] == pytest.approx(0.1)

    def test_missing_stages_degrade_gracefully(self):
        from repro.observe import build_task_traces

        evs = [_task("a", "submitted", 0.0), _task("a", "picked_up", 0.2)]
        (tr,) = build_task_traces(evs)
        assert [s.name for s in tr.spans] == ["queue-wait"]
        assert not tr.flags

    def test_out_of_order_pair_flagged_not_negative(self):
        from repro.observe import build_task_traces

        evs = _lifecycle("a")
        # Clock skew: running recorded before its dispatched.
        evs[4] = _task("a", "running", 0.25)   # dispatched is at 0.3
        evs[3] = _task("a", "dispatched", 0.3)
        (tr,) = build_task_traces(evs)
        assert "out-of-order:dispatch" in tr.flags
        assert all(s.duration >= 0 for s in tr.spans)

    def test_failed_task_run_span_ends_at_failed(self):
        from repro.observe import build_task_traces

        (tr,) = build_task_traces(_lifecycle("a", fail=True))
        assert not tr.ok
        names = [s.name for s in tr.spans]
        assert "run" in names and "result-wait" in names

    def test_trace_context_rides_events_and_retry_links(self):
        from repro.core import TraceContext
        from repro.observe import build_task_traces

        ctx = TraceContext.new()
        child = ctx.child()
        assert child.trace_id == ctx.trace_id
        assert child.parent_span_id == ctx.span_id
        assert child.span_id != ctx.span_id
        (tr,) = build_task_traces(_lifecycle("a", **ctx.as_dict()))
        assert tr.trace_id == ctx.trace_id and tr.span_id == ctx.span_id

    def test_results_carry_trace_context_end_to_end(self):
        log = EventLog()
        _, results = _run_tasks(log, n_tasks=4)
        assert all(r.trace is not None for r in results)
        assert len({r.trace.trace_id for r in results}) == 4
        for ev in log.events():
            if ev.kind == "task":
                assert "trace_id" in ev.info

    def test_perfetto_export_shape(self, tmp_path):
        from repro.observe import export_perfetto

        log = EventLog(jsonl_path=str(tmp_path / "ev.jsonl"))
        _run_tasks(log, n_tasks=3)
        log.profile("kernel.x", t_start=0.5, wall_s=0.01, device_s=0.004)
        log.close()
        doc = export_perfetto(str(tmp_path / "ev.jsonl"),
                              str(tmp_path / "trace.json"))
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len([e for e in xs if e["cat"] == "task"]) >= 3 * 5
        assert len([e for e in xs if e["cat"] == "profile"]) == 1
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert json.loads((tmp_path / "trace.json").read_text())


def _fed_double(x):
    return x * 2


class TestFederatedTrace:
    """The federated observability acceptance: a spawned-server run
    (ServerSpec(in_process=False)) writes parent + child JSONL logs that
    merge into one causal trace with zero lifecycle gaps."""

    def test_merged_cross_process_trace_is_complete(self, tmp_path):
        from repro.app import (
            AppSpec, ColmenaApp, ObserveSpec, QueueSpec, ServerSpec,
        )
        from repro.observe import build_task_traces, merge_jsonl

        jsonl = str(tmp_path / "events.jsonl")
        spec = AppSpec(
            tasks={"double": _fed_double},
            queues=QueueSpec(backend="pipe"),
            pools={"default": 2},
            server=ServerSpec(in_process=False),
            observe=ObserveSpec(jsonl_path=jsonl),
        )
        server_jsonl = spec.observe.resolved_server_jsonl()
        app = ColmenaApp(spec)
        with app.run(timeout=120) as handle:
            for i in range(6):
                handle.queues.send_inputs(i, method="double")
            results = [handle.queues.get_result(timeout=60) for _ in range(6)]
        assert all(r is not None and r.success for r in results)

        merged = EventLog(capacity=1 << 18)
        for ev in merge_jsonl([jsonl, server_jsonl]):
            merged.emit(ev)
        assert lifecycle_gaps(merged) == {}
        assert lifecycle_order_violations(merged) == []
        traces = build_task_traces(merged)
        assert len(traces) == 6
        for tr in traces:
            assert tr.trace_id is not None
            sites = {s.site for s in tr.spans}
            assert len(sites) == 2  # spans land on both sides of the pipe


class TestEventLogDurability:
    def test_jsonl_lines_visible_before_close(self, tmp_path):
        """Line-buffered sink: a kill -9'd child's log is still readable."""
        path = tmp_path / "ev.jsonl"
        log = EventLog(jsonl_path=str(path))
        log.gauge("slots", 1, pool="p")
        log.gauge("slots", 2, pool="p")
        rows = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(rows) == 2  # visible without close()
        log.close()

    def test_close_is_idempotent(self, tmp_path):
        log = EventLog(jsonl_path=str(tmp_path / "ev.jsonl"))
        log.gauge("slots", 1, pool="p")
        log.close()
        log.close()

    def test_torn_tail_line_skipped_on_load(self, tmp_path):
        from repro.observe import load_jsonl

        path = tmp_path / "ev.jsonl"
        log = EventLog(jsonl_path=str(path))
        log.gauge("slots", 1, pool="p")
        log.close()
        with open(path, "a") as fh:
            fh.write('{"t": 1.0, "kind": "gau')  # SIGKILL mid-write
        events = load_jsonl(str(path))
        assert len(events) == 1 and events[0].value == 1.0
        assert events[0].info["site"] == "ev"

    def test_size_based_rotation(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        log = EventLog(jsonl_path=str(path), rotate_bytes=2048, rotate_keep=2)
        for i in range(200):
            log.gauge("slots", i, pool="p")
        log.close()
        assert path.exists()
        assert (tmp_path / "ev.jsonl.1").exists()
        # Every generation holds valid JSONL; total rows capped by keep.
        for p in (path, tmp_path / "ev.jsonl.1"):
            for line in p.read_text().splitlines():
                json.loads(line)


class TestArrivalRateScaling:
    """Satellite: the ElasticScaler folds the event-log arrival rate into
    its sizing decisions so fleets pre-grow ahead of bursts."""

    def _scaler(self, log, n=1, lo=1, hi=8, **policy_kw):
        from repro.app import PoolSpec
        from repro.observe import ElasticPolicy, ElasticScaler

        spec = PoolSpec("p", size=n, min_size=lo, max_size=hi, warm_capacity=0)
        pool = spec.build(event_log=log)
        policy = ElasticPolicy(idle_grace_ticks=1, **policy_kw)
        scaler = ElasticScaler({"p": pool}, {"p": spec},
                               policy=policy, event_log=log)
        return pool, scaler

    def test_dispatched_events_feed_rate_ema(self):
        log = EventLog()
        pool, scaler = self._scaler(log)
        scaler._update_rates()          # arm the clock
        for i in range(10):
            log.emit(_task(f"t{i}", "dispatched", float(i), pool="p"))
        time.sleep(0.05)
        scaler._update_rates()
        assert scaler._rate_ema["p"] > 0
        assert scaler.expected_arrivals("p") > 0
        gauges = [e for e in log.events()
                  if e.kind == "gauge" and e.stage == "arrival_rate"]
        assert gauges and gauges[-1].pool == "p"
        scaler.stop()
        pool.shutdown()

    def test_pre_grow_ahead_of_queue(self):
        """High arrival rate + empty queue still grows the fleet."""
        log = EventLog()
        pool, scaler = self._scaler(log, n=1)
        scaler._rate_ema["p"] = 100.0   # 100 tasks/s smoothed
        scaler._rate_t = time.monotonic()
        target = scaler._decide("p", pool)
        assert target is not None and target > pool.n_workers
        pool.shutdown()
        scaler.stop()

    def test_expected_arrivals_hold_capacity(self):
        """Imminent arrivals reset the idle clock instead of shrinking."""
        log = EventLog()
        pool, scaler = self._scaler(log, n=2)
        scaler._rate_ema["p"] = 3.0     # ~0.6 expected in the window
        scaler._idle_ticks["p"] = 5
        assert scaler._decide("p", pool) is None
        assert scaler._idle_ticks["p"] == 0
        # Rate decays to zero: the idle-grace shrink path resumes.
        scaler._rate_ema["p"] = 0.0
        target = None
        for _ in range(3):
            target = scaler._decide("p", pool)
            if target is not None:
                break
        assert target is not None and target < 2
        pool.shutdown()
        scaler.stop()

    def test_rebind_moves_subscription(self):
        log1, log2 = EventLog(), EventLog()
        _, scaler = self._scaler(log1)
        scaler.rebind_event_log(log2)
        log1.emit(_task("a", "dispatched", 0.0, pool="p"))
        log2.emit(_task("b", "dispatched", 0.0, pool="p"))
        assert scaler._arrival_counts["p"] == 1  # only log2 counted
        scaler.stop()


class TestMetricsExport:
    def test_prometheus_text_format(self):
        log = EventLog()
        _run_tasks(log, n_tasks=5)
        agg = MetricsAggregator(log)
        text = agg.prometheus_text(slots_by_pool={"alpha": 2, "beta": 2})
        assert "# TYPE repro_pool_completed counter" in text
        assert 'repro_pool_completed{pool="alpha"} 3' in text
        assert "repro_makespan_seconds" in text
        assert 'repro_pool_utilization{pool="total"}' in text
        assert 'repro_method_latency_seconds{method="work",quantile="0.5"}' in text
        assert text.endswith("\n")
        for line in text.splitlines():
            assert line.startswith("#") or " " in line

    def test_snapshot_is_json_safe(self):
        log = EventLog()
        _run_tasks(log, n_tasks=4)
        log.profile("kernel.x", t_start=0.0, wall_s=0.01)
        agg = MetricsAggregator(log)
        snap = agg.snapshot(slots_by_pool={"alpha": 2})
        doc = json.loads(json.dumps(snap))
        assert doc["methods"]["work"]["count"] == 4
        assert doc["profiles"]["kernel.x"]["count"] == 1

    def test_exporter_writes_prom_and_snapshot(self, tmp_path):
        from repro.observe import ExportSpec, MetricsExporter

        log = EventLog()
        _run_tasks(log, n_tasks=3)
        exporter = MetricsExporter(
            log, spec=ExportSpec(dir=str(tmp_path), interval_s=60),
            slots_by_pool={"alpha": 2, "beta": 2})
        exporter.write_once()
        prom = (tmp_path / "metrics.prom").read_text()
        assert "repro_pool_completed" in prom
        snap = json.loads((tmp_path / "snapshot.json").read_text())
        assert snap["methods"]["work"]["count"] == 3
        assert "ts" in snap

    def test_exporter_background_thread(self, tmp_path):
        from repro.observe import ExportSpec, MetricsExporter

        log = EventLog()
        exporter = MetricsExporter(
            log, spec=ExportSpec(dir=str(tmp_path), interval_s=0.05))
        exporter.start()
        _run_tasks(log, n_tasks=2)
        time.sleep(0.15)
        exporter.stop()
        snap = json.loads((tmp_path / "snapshot.json").read_text())
        assert snap["methods"]["work"]["count"] == 2

    def test_exporter_rebind_no_double_count(self, tmp_path):
        """After ``rebind`` the exporter must aggregate only the new log:
        late events still arriving on the old one are another run's."""
        from repro.observe import ExportSpec, MetricsExporter

        log1 = EventLog()
        _run_tasks(log1, n_tasks=4)
        exporter = MetricsExporter(log1, spec=ExportSpec(dir=str(tmp_path)))
        exporter.write_once()
        assert json.loads((tmp_path / "snapshot.json").read_text())[
            "methods"]["work"]["count"] == 4
        log2 = EventLog()
        exporter.rebind(log2)
        _run_tasks(log1, n_tasks=5)  # late arrivals on the old log: ignored
        _run_tasks(log2, n_tasks=2)
        exporter.write_once()
        snap = json.loads((tmp_path / "snapshot.json").read_text())
        assert snap["methods"]["work"]["count"] == 2

    def test_jsonl_rotation_no_double_count(self, tmp_path):
        """Every event lands in exactly one rotated generation — loading
        all generations back recovers each task lifecycle exactly once."""
        from repro.observe.trace import load_jsonl

        path = tmp_path / "ev.jsonl"
        log = EventLog(jsonl_path=str(path), rotate_bytes=4096, rotate_keep=8)
        _, results = _run_tasks(log, n_tasks=24)
        assert all(r.success for r in results)
        log.close()
        generations = sorted(tmp_path.glob("ev.jsonl*"))
        assert len(generations) >= 2, "rotation never triggered"
        events = [ev for g in generations for ev in load_jsonl(str(g))]
        received = [ev for ev in events if ev.stage == "result_received"]
        assert len(received) == 24
        assert len({ev.task_id for ev in received}) == 24

    def test_observe_spec_export_knob(self, tmp_path):
        from repro.app import AppSpec, ColmenaApp, ObserveSpec

        app = ColmenaApp(AppSpec(
            tasks={"double": _fed_double},
            pools={"default": 2},
            observe=ObserveSpec(export=str(tmp_path)),
        ))
        with app.run(timeout=60) as handle:
            handle.queues.send_inputs(3, method="double")
            assert handle.queues.get_result(timeout=30).success
        assert (tmp_path / "metrics.prom").exists()
        assert json.loads((tmp_path / "snapshot.json").read_text())


class TestBenchTrajectory:
    def test_recorder_writes_schema(self, tmp_path):
        from repro.observe import BenchRecorder, load_bench

        rec = BenchRecorder("demo", out_dir=str(tmp_path))
        rec.metric("speedup_x", 3.2, unit="x", gate=(">=", 2.0))
        rec.metric("latency_us", 120.0, unit="us")
        path = rec.finish(ok=True)
        doc = load_bench(path)
        assert doc["name"] == "demo" and doc["schema"] == 1
        assert doc["metrics"]["speedup_x"]["passed"] is True
        assert doc["gates_passed"] and doc["passed"]
        assert "python" in doc["env"]
        assert doc["commit"] is None or len(doc["commit"]) == 40

    def test_env_fingerprint_starts_no_backend(self):
        """A recording process must leave the chip to the measuring one."""
        import os
        import subprocess
        import sys

        code = ("from jax._src import xla_bridge; "
                "from repro.observe import env_fingerprint; fp = env_fingerprint(); "
                "assert fp['jax'] and 'jax_backend' not in fp, fp; "
                "assert not xla_bridge.backends_are_initialized()")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, env=env, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]

    def test_failed_gate_fails_suite(self, tmp_path):
        from repro.observe import BenchRecorder, load_bench

        rec = BenchRecorder("demo", out_dir=str(tmp_path))
        rec.metric("speedup_x", 1.1, unit="x", gate=(">=", 2.0))
        doc = load_bench(rec.finish(ok=True))
        assert doc["metrics"]["speedup_x"]["passed"] is False
        assert not doc["gates_passed"] and not doc["passed"]

    def test_diff_regression_direction(self):
        from repro.observe import bench_diff

        old = {"name": "demo", "commit": "a" * 40, "metrics": {
            "speedup_x": {"value": 3.0, "gate": {"op": ">=", "threshold": 2.0}},
            "latency_us": {"value": 100.0, "gate": {"op": "<=", "threshold": 500.0}},
            "free": {"value": 1.0},
        }}
        new = {"name": "demo", "commit": "b" * 40, "metrics": {
            "speedup_x": {"value": 2.0, "gate": {"op": ">=", "threshold": 2.0}},
            "latency_us": {"value": 90.0, "gate": {"op": "<=", "threshold": 500.0}},
            "free": {"value": 5.0},
        }}
        diff = bench_diff(old, new)
        assert diff["metrics"]["speedup_x"]["status"] == "regressed"
        assert diff["metrics"]["latency_us"]["status"] == "improved"
        assert diff["metrics"]["free"]["status"] == "changed"  # ungated
        assert diff["regressions"] == ["speedup_x"] and not diff["ok"]

    def test_diff_within_tolerance_unchanged(self):
        from repro.observe import bench_diff

        old = {"name": "d", "metrics": {"x": {"value": 100.0, "gate": {"op": ">=", "threshold": 1}}}}
        new = {"name": "d", "metrics": {"x": {"value": 97.0, "gate": {"op": ">=", "threshold": 1}}}}
        diff = bench_diff(old, new, rel_tol=0.05)
        assert diff["metrics"]["x"]["status"] == "unchanged" and diff["ok"]

    def test_render_and_cli_diff(self, tmp_path, capsys):
        from repro.observe import BenchRecorder, render_diff
        from repro.observe.__main__ import main as cli_main
        from repro.observe.bench import diff_paths

        for d, val in (("old", 4.0), ("new", 1.5)):
            rec = BenchRecorder("demo", out_dir=str(tmp_path / d))
            rec.metric("speedup_x", val, unit="x", gate=(">=", 2.0))
            rec.finish(ok=True)
        old = str(tmp_path / "old" / "BENCH_demo.json")
        new = str(tmp_path / "new" / "BENCH_demo.json")
        text = render_diff(diff_paths(old, new))
        assert "REGRESSED: speedup_x" in text
        assert cli_main(["bench", "diff", old, new]) == 0  # soft by default
        assert cli_main(["bench", "diff", old, new, "--fail-on-regress"]) == 1
        assert cli_main(["bench", "diff",
                         str(tmp_path / "old"), str(tmp_path / "new"),
                         "--fail-on-regress"]) == 1
        capsys.readouterr()

    def test_specfile_roundtrip_observe_knobs(self, tmp_path):
        from repro.app import AppSpec, ObserveSpec
        from repro.core.specfile import spec_from_dict, spec_to_dict

        spec = AppSpec(
            tasks={"double": _fed_double},
            observe=ObserveSpec(
                jsonl_path="ev.jsonl", rotate_bytes=1 << 20, rotate_keep=2,
                export={"dir": "obs", "interval_s": 2.0}),
        )
        d = spec_to_dict(spec)
        assert d["observe"]["rotate_bytes"] == 1 << 20
        assert d["observe"]["export"]["dir"] == "obs"
        back = spec_from_dict(d)
        assert back.observe.rotate_bytes == 1 << 20
        assert back.observe.resolved_server_jsonl() == "ev.server.jsonl"

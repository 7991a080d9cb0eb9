"""The engine's spans and the step's scopes, reduced: synthetic events, the
CPU step's HLO text, whole CPU runs, and traces recorded on the chip."""

import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, scopes, spans, trace
from bench.reference import dense

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "bench" / "testdata"
NEW_METRICS = ("engine.host_ms", "engine.prefill_token_ms", "model.cache_write_ms", "step.copy_ms")


def test_gap_pieces_go_to_the_innermost_span():
    # window 0..1000; the device runs 0-100 and 900-1000; the gap 100-900
    # straddles a step's tail and the next step's head.
    ops = [("%fusion.1 = a", 0, 100), ("%fusion.2 = b", 900, 100)]
    host = [("bench.window", 0, 1000),
            ("engine.step", 0, 400), ("serve.device_get", 50, 150), ("serve.bookkeeping", 200, 150),
            ("serve.hooks", 250, 20),
            ("engine.step", 500, 500), ("serve.dispatch", 550, 340)]
    r = spans.reduce_events([(ops, [])], host)
    # pieces: device_get 100-200, bookkeeping 200-250 and 270-350, hooks 250-270,
    # engine.step 350-400 and 500-550, host.other 400-500, dispatch 550-890, engine.step 890-900
    assert r.idle_by_span == pytest.approx({
        "serve.device_get": 100e-9, "serve.bookkeeping": 130e-9, "serve.hooks": 20e-9,
        "engine.step": 110e-9, "host.other": 100e-9, "serve.dispatch": 340e-9})
    assert r.gaps == [("serve.dispatch", pytest.approx(800e-9))]
    assert r.span_s["serve.dispatch"] == (1, pytest.approx(340e-9))
    assert r.span_s["engine.step"] == (2, pytest.approx(900e-9))
    assert "serve.admit" not in r.span_s


def test_gaps_read_as_before_without_engine_spans():
    ops = [("%while.1 = a", 1100, 300), ("%decode_attention.3 = b", 1150, 100),
           ("%copy.7 = c", 1600, 100), ("%fusion.9 = d", 500, 100)]
    modules = [("jit_serve_step(1)", 1100, 300), ("jit_serve_step(1)", 1600, 100)]
    host = [("bench.window", 1000, 1000), ("engine.step", 1000, 450),
            ("bench.wait", 1450, 140), ("engine.step", 1590, 400), ("other", 0, 5000)]
    old = trace.reduce_events([(ops, modules)], host)
    new = spans.reduce_events([(ops, modules)], [e for e in host if spans.wanted(e[0])])
    assert new.gaps == old.gaps
    assert sum(new.idle_by_span.values()) == pytest.approx(old.window_s - old.busy_s)


def test_device_ops_split_by_program_execution():
    ops = [("%while.1 = a", 100, 300), ("%fusion.2 = b", 150, 100), ("%copy.3 = c", 420, 30),
           ("%add.4 = d", 500, 10), ("%copy.3 = c", 700, 20), ("%stray.5 = e", 950, 10)]
    modules = [("jit_serve_step(7)", 100, 360), ("jit_add(8)", 500, 10), ("jit_serve_step(7)", 690, 40)]
    r = spans.reduce_events([(ops, modules)], [("bench.window", 0, 1000)])
    assert r.program_calls == {"jit_serve_step": 2, "jit_add": 1}
    assert r.program_op_s == {
        "jit_serve_step": pytest.approx({"while.1": 200e-9, "fusion.2": 100e-9, "copy.3": 50e-9}),
        "jit_add": pytest.approx({"add.4": 10e-9}),
        spans.NO_PROGRAM: pytest.approx({"stray.5": 10e-9})}


HLO = """\
HloModule jit_serve_step, is_scheduled=true

%fused_computation (p: bf16[8]) -> bf16[8] {
  ROOT %mul.1 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(serve_step)/layers/while/body/attn/kv_cache.update/mul"}
}

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %multiply_add_fusion.3 = bf16[8]{0:T(128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_step)/layers/while/body/closed_call/attn/kv_cache.update/add" stack_frame_id=3}
  %add_dynamic-update-slice_fusion.3 = (bf16[32,8]{1,0}, bf16[8]{0}) fusion(%a), kind=kLoop, metadata={op_name="jit(serve_step)/layers/while/body/dynamic_update_slice"}
  %fusion.103 = bf16[8,8192]{1,0:T(8,128)(2,1)S(1)} fusion(%a), kind=kOutput, metadata={op_name="jit(serve_step)/layers/while/body/closed_call/mlp/bsd,df->bsf/dot_general"}
  %rmsnorm.9 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(serve_step)/layers/while/body/closed_call/rmsnorm/pallas_call"}
  %copy.76 = bf16[32,8]{1,0:T(8,128)(2,1)} copy(%a), backend_config={"x":1}
  %copy-start = (bf16[8]{0}, bf16[8]{0}, u32[]{:S(2)}) copy-start(%a)
  %copy-done = bf16[8]{0:T(128)S(1)} copy-done(%copy-start)
  %params__embed__.1 = bf16[8]{0} parameter(0), metadata={op_name="params[\\'embed\\']"}
  ROOT %argmax.2 = s32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(serve_step)/sample/argmax"}
}
"""


def test_hlo_ops_map_to_the_innermost_scope():
    p = scopes.op_places(HLO)
    assert p["multiply_add_fusion.3"] == ("kv_cache.update", "add")
    assert p["add_dynamic-update-slice_fusion.3"] == ("layers", "dynamic_update_slice")
    assert p["fusion.103"].scope == "mlp" and p["rmsnorm.9"].scope == "layers"
    assert p["argmax.2"].scope == "sample" and p["params__embed__.1"].scope == scopes.UNSCOPED
    assert p["copy.76"].scope == "xla.copy" and p["copy-start"].scope == "xla.copy-start"
    assert p["copy-done"].scope == "xla.copy-done"
    op_s = {"multiply_add_fusion.3": 2.0, "add_dynamic-update-slice_fusion.3": 3.0, "fusion.103": 5.0,
            "copy.76": 7.0, "copy-done": 1.0, "missing.1": 0.5}
    assert scopes.cache_write_s(op_s, p) == 5.0
    assert scopes.copy_s(op_s, p) == 8.0
    assert scopes.by_scope(op_s, p) == {"kv_cache.update": 2.0, "layers": 3.0, "mlp": 5.0,
                                        "xla.copy": 7.0, "xla.copy-done": 1.0, "unknown": 0.5}


def test_scope_list_matches_the_program():
    """Both directions between the benchmark's scope list and the program's.

    Every scope the benchmark reads is one the program marks. Every scope
    the program marks that the list lacks (one that another family's step
    adds) is read by a reader under ``bench/metrics`` that passes it to
    ``op_places``, and passing it takes no operation of the step of any
    configuration in ``BENCHMARK.json`` away from the write path that
    ``model.cache_write_ms`` reads: the readers of the list still see
    every operation they read."""
    import ast
    import json

    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.models.scopes import DECODE_SCOPES
    from repro.serve import ServingEngine

    assert set(scopes.SCOPES) <= set(DECODE_SCOPES)
    extra = tuple(s for s in DECODE_SCOPES if s not in scopes.SCOPES)
    named = {}
    for path in sorted((ROOT / "bench" / "metrics").glob("*.py")):
        src = path.read_text()
        if "op_places(" in src:
            named[path.stem] = {n.value for n in ast.walk(ast.parse(src))
                                if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    for scope in extra:
        assert any(scope in consts for consts in named.values()), \
            f"the program marks {scope!r}, which no reader under bench/metrics passes to op_places"

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for arch in sorted({json.loads((ROOT / c["file"]).read_text())["arch"] for c in spec["configs"]}):
        m = build_model(smoke_config(arch))
        text = ServingEngine(m, m.init(jax.random.PRNGKey(0)), n_slots=2,
                             max_len=64).compile().as_text()
        listed, wider = scopes.op_places(text), scopes.op_places(text, scopes.SCOPES + extra)
        write = {op for op, p in listed.items()
                 if p.scope == "kv_cache.update" or p == ("layers", "dynamic_update_slice")}
        assert write, arch
        assert {op: wider[op] for op in write} == {op: listed[op] for op in write}, arch


def test_a_scope_of_another_family_is_read_by_its_name():
    line = ('  %fusion.7 = bf16[8]{0} fusion(%a), kind=kLoop, metadata={op_name='
            '"jit(serve_step)/layers/while/body/mlp/experts/dot_general"}')
    hlo = "HloModule jit_serve_step\n\nENTRY %main {\n" + line + "\n}\n"
    assert scopes.op_places(hlo)["fusion.7"] == ("mlp", "dot_general")
    assert scopes.op_places(hlo, scopes.SCOPES + ("experts",))["fusion.7"] == ("experts", "dot_general")


def test_compiled_cpu_step_maps_write_path_ops():
    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serve import ServingEngine

    m = build_model(smoke_config("phi4-mini-3.8b"))
    text = ServingEngine(m, m.init(jax.random.PRNGKey(0)), n_slots=2, max_len=64).compile().as_text()
    found = {p.scope for p in scopes.op_places(text).values()}
    assert {"kv_cache.update", "mlp", "xla.copy", "layers", "attn", "unembed", "sample"} <= found


def parent_run(**over):
    stats = SimpleNamespace(steps=10, batch_occupancy_sum=10.0)
    base = dict(model={}, reference=dense, seconds=1.0, t_open=0.0, t_close=1.0, setup_s=1.0, rec=run.Record(),
                stats_open=stats, stats_close=stats, peak_bytes=None, peaks=None,
                trace=trace.reduce_events([], [("bench.window", 0, 10)]))
    base.update(over)
    return run.Run(**base)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_are_silent_on_a_parent_shaped_run(metric):
    assert run.reader(metric)(parent_run()) is None


def test_recorded_trace_reads_as_before():
    """The trace recorded before the engine had spans: gaps and device numbers unchanged."""
    old = trace.load(str(TESTDATA / "phi4_steps"))
    new = spans.load(str(TESTDATA / "phi4_steps"))
    assert new.gaps == old.gaps
    r = parent_run(trace=old)
    assert run.reader("step.device_ms")(r) == pytest.approx(30.851873667, rel=1e-6)
    assert run.reader("device.idle_share")(r) == pytest.approx(100 * (1 - old.busy_s / old.window_s))
    n, sec = old.module("jit_serve_step")
    assert new.program_calls["jit_serve_step"] == n
    assert sum(new.program_op_s["jit_serve_step"].values()) == pytest.approx(sec, rel=1e-3)


def test_traced_cpu_run_reads_the_engine_spans(monkeypatch):
    import json

    from bench import layers
    from test_bench_harness import STD, cell_named, smoke

    splits, logged = [], []

    def split(got, hlo_text, log):
        splits.append((got, hlo_text, layers_split(got, hlo_text, log)))

    layers_split = layers.split
    monkeypatch.setattr(layers, "split", split)
    cell, cfg = smoke(cell_named("phi4-mini.agent-decode"))
    res = run.run_cell(cell, 2147480001, 2.0, True, require_tpu=False, cfg=cfg, std=STD,
                       log=logged.append)
    assert res["correct"], res["checks"]
    # no TPU plane on the CPU: the device readers stay silent, the host ones read
    assert {"engine.host_ms", "engine.prefill_token_ms"} <= set(res["metrics"])
    assert not {"model.cache_write_ms", "step.copy_ms"} & set(res["metrics"])
    ((got, hlo_text, out),) = splits
    assert hlo_text.startswith("HloModule jit_serve_step")
    assert res["breakdown"]["idle_gaps"] == [[n, s] for n, s in got.gaps[:10]]
    prefix = "engine counters over the window: "
    (line,) = [m for m in logged if m.startswith(prefix)]
    counters = json.loads(line[len(prefix):])
    assert counters["prefill_tokens"] == counters["prefill_calls"] > 0
    assert out["span_ms"]["serve.dispatch"][0] == counters["steps"]
    prefill_n, prefill_ms = out["span_ms"]["serve.prefill"]
    assert res["metrics"]["engine.prefill_token_ms"]["value"] == pytest.approx(
        prefill_n * prefill_ms / counters["prefill_tokens"])


def test_recorded_admission_trace():
    """Three ``step()`` calls of phi4-mini on one v5e, recorded by
    ``bench/layers.py --record``: a request finishes, the next is admitted
    with one prefill call, one more decode; with the step's HLO text."""
    d = TESTDATA / "phi4_admit"
    layers = spans.load(str(d))
    with gzip.open(d / "step.hlo.gz", "rt") as f:
        hlo = f.read()
    assert layers.program_calls["jit_serve_step"] == 4
    counts = {k: n for k, (n, _) in layers.span_s.items()}
    assert counts["serve.admit"] == counts["serve.prefill"] == 1
    assert counts["serve.dispatch"] == counts["serve.device_get"] == counts["engine.step"] == 3
    # the gaps bench/trace.py finds, named by the engine's spans instead of engine.step
    old = trace.load(str(d))
    assert [s for _, s in layers.gaps] == [s for _, s in old.gaps]
    assert "engine.step" not in {n for n, _ in layers.gaps[:3]}
    op_s = layers.program_op_s["jit_serve_step"]
    places = scopes.op_places(hlo)
    by = scopes.by_scope(op_s, places)
    assert by.get("unknown", 0.0) <= 0.02 * sum(op_s.values())
    assert {"layers", "attn", "kv_cache.update", "mlp", "unembed", "xla.copy"} <= set(by)
    r = SimpleNamespace(layers=layers, hlo_text=hlo, stats_open=SimpleNamespace(prefill_tokens=24),
                        stats_close=SimpleNamespace(prefill_tokens=25))
    got = {m: run.reader(m)(r) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the write path and the copies are half of phi4-mini's ~31 ms step
    assert 12 < got["model.cache_write_ms"] + got["step.copy_ms"] < 24
    assert got["engine.prefill_token_ms"] == pytest.approx(1e3 * layers.span_s["serve.prefill"][1])


@pytest.mark.parametrize("recorded", ["phi4_steps", "phi4_admit"])
def test_one_read_of_the_trace_gives_both_reductions(recorded):
    """A traced run reads the trace once, keeping the engine's spans, cuts
    it to the window once, and reduces it both ways: as each of the two
    loaders would alone, less the gaps named by the benchmark's spans."""
    from dataclasses import replace

    devices, host = trace.events(str(TESTDATA / recorded), spans.wanted)
    assert trace.reduce_events(devices, host) == trace.load(str(TESTDATA / recorded))
    assert spans.reduce_events(devices, host) == spans.load(str(TESTDATA / recorded))
    window = trace.clip(devices, host)
    alone = trace.load(str(TESTDATA / recorded))
    assert alone.gaps and trace.reduce_window(window) == replace(alone, gaps=[])
    assert spans.reduce_window(window, host) == spans.load(str(TESTDATA / recorded))


def test_record_writes_three_steps_and_the_step_text(tmp_path):
    from bench import layers
    from test_bench_harness import cell_named, smoke

    cell, cfg = smoke(cell_named("phi4-mini.agent-decode"))
    layers.record(cell, 3, str(tmp_path), log=lambda m: None, require_tpu=False, cfg=cfg)
    got = spans.load(str(tmp_path))
    counts = {k: n for k, (n, _) in got.span_s.items()}
    assert counts["engine.step"] == counts["serve.dispatch"] == 3
    assert counts["serve.admit"] == counts["serve.prefill"] == 1
    with gzip.open(tmp_path / "step.hlo.gz", "rt") as f:
        assert f.read().startswith("HloModule jit_serve_step,")

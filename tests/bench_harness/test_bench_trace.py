"""Trace reduction on synthetic events, and on a trace recorded on the chip."""

from pathlib import Path

import pytest

from bench import trace

TESTDATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"


def test_op_and_module_names():
    assert trace.op_name("%fusion.103 = bf16[8,8192]{1,0} fusion(bf16[8,3072] %x)") == "fusion.103"
    assert trace.op_name("%decode_attention.4 = bf16[64,3,128] custom-call(...)") == "decode_attention.4"
    assert trace.module_name("jit_serve_step(16752017127515257278)") == "jit_serve_step"


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [(0, 4), (5, 7), (8, 9)]


def test_self_time_subtracts_nested_ops():
    ops = [("%while.1 = x", 0, 100), ("%fusion.1 = x", 10, 30), ("%decode_attention.2 = x", 50, 20),
           ("%fusion.1 = x", 200, 10)]
    got = trace.self_times([(trace.op_name(n), s, d) for n, s, d in ops])
    assert got == pytest.approx({"while.1": 50e-9, "fusion.1": 40e-9, "decode_attention.2": 20e-9})


def synthetic():
    # window 1000..2000 ns; the device runs 1100-1400 (a step with a kernel
    # nested in a while) and 1600-1700; the host steps, then waits.
    ops = [("%while.1 = a", 1100, 300), ("%decode_attention.3 = b", 1150, 100),
           ("%copy.7 = c", 1600, 100), ("%fusion.9 = d", 500, 100)]
    modules = [("jit_serve_step(1)", 1100, 300), ("jit_serve_step(1)", 1600, 100),
               ("jit_serve_step(1)", 2500, 100)]
    host = [("bench.window", 1000, 1000), ("engine.step", 1000, 450),
            ("bench.wait", 1450, 140), ("engine.step", 1590, 400), ("other", 0, 5000)]
    return trace.reduce_events([(ops, modules)], host)


def test_busy_idle_and_modules_within_the_window():
    r = synthetic()
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(400e-9)
    assert r.module("jit_serve_step") == (2, pytest.approx(400e-9))
    assert r.kernel_s("decode_attention") == pytest.approx(100e-9)
    assert r.kernel_calls("decode_attention") == 1 and r.op_calls["copy.7"] == 1
    assert r.op_self_s["while.1"] == pytest.approx(200e-9)
    assert "fusion.9" not in r.op_self_s                       # before the window


def test_idle_gaps_are_named_by_the_host_span():
    r = synthetic()
    # gaps: 1000-1100 (step), 1400-1600 (wait 140 of 200), 1700-2000 (step)
    assert r.gaps == [("engine.step", pytest.approx(300e-9)), ("bench.wait", pytest.approx(200e-9)),
                      ("engine.step", pytest.approx(100e-9))]
    assert r.top_ops(2)[0] == ["while.1", pytest.approx(200e-9)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events([], [("engine.step", 0, 10)])


def test_recorded_chip_trace():
    """A few serve steps of phi4-mini on one v5e, traced by ``bench/run.py``."""
    r = trace.load(str(TESTDATA / "phi4_steps"))
    assert r.n_devices == 1
    n, sec = r.module("jit_serve_step")
    assert n >= 1 and sec > 0
    assert 0 < r.busy_s <= r.window_s
    assert r.kernel_s("decode_attention") > 0 and r.kernel_s("rmsnorm") > 0
    assert r.kernel_s("decode_attention") < sec
    # one kernel call per layer (phi4-mini has 32) in every serve-step call
    assert r.kernel_calls("decode_attention") == 32 * n
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert {name for name, _ in r.gaps} <= set(trace.SPANS) | {"host.other"}

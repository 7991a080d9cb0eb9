"""``decode_attention.fetch_share``: the share of the cache's blocks that
the decode-attention kernel fetched over the window's decode steps, from
the engine's counters; silent for a program without them."""

from types import SimpleNamespace

import pytest

from bench import run
from repro.kernels.decode_attention import kernel as kernel_mod

from test_bench_harness import go, smoke

READ = run.reader("decode_attention.fetch_share")


def test_reads_the_window_change_of_the_counters():
    opened = SimpleNamespace(steps=10, kv_blocks_read=100, kv_blocks_held=400)
    closed = SimpleNamespace(steps=30, kv_blocks_read=250, kv_blocks_held=1400)
    assert READ(SimpleNamespace(stats_open=opened, stats_close=closed)) == pytest.approx(15.0)


@pytest.mark.parametrize("stats", [SimpleNamespace(steps=5),
                                   SimpleNamespace(steps=5, kv_blocks_read=0, kv_blocks_held=0)],
                         ids=["no counters", "no decode step"])
def test_silent_without_counters_or_steps(stats):
    assert READ(SimpleNamespace(stats_open=stats, stats_close=stats)) is None


def test_traced_run_reports_the_share(monkeypatch):
    """A CPU run of the phi4-mini cell at a smoke width, with 8-position
    blocks so that slots of a 512-position cache fetch a part of them."""
    monkeypatch.setattr(kernel_mod, "TARGET_BLOCK_BYTES", 1)
    cell, cfg = smoke(run.find_cell("phi4-mini.agent-decode"))
    res = go(cell, cfg, trace=True)
    assert res["correct"], res["checks"]
    share = res["metrics"]["decode_attention.fetch_share"]
    assert share["unit"] == "%" and 0 < share["value"] < 100

"""Unit tests: roofline HLO parsing, report generation, and the
Colmena-steered training driver (including preemption recovery)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st

from repro.launch.roofline import (
    CollectiveStats,
    RooflineReport,
    _type_bytes,
    model_flops,
    parse_collectives,
)
from repro.configs import get_config
from repro.configs.base import SHAPES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHloParsing:
    def test_type_bytes(self):
        assert _type_bytes("bf16[128,4096]{1,0}") == 128 * 4096 * 2
        assert _type_bytes("f32[16]") == 64
        assert _type_bytes("(f32[2,2], bf16[4])") == 16 + 8
        assert _type_bytes("pred[8]") == 8

    def test_parse_ring_conventions(self):
        hlo = "\n".join([
            "%ag = bf16[64,64]{1,0} all-gather(%x), replica_groups=[16,16]<=[256], dimensions={0}",
            "%ar = f32[32]{0} all-reduce(%y), replica_groups=[1,256]<=[256], to_apply=%add",
            "%rs = bf16[8,8]{1,0} reduce-scatter(%z), replica_groups=[16,16]<=[256]",
            "%cp = f32[4,4]{1,0} collective-permute(%w), source_target_pairs={{0,1}}",
        ])
        stats = parse_collectives(hlo, 256)
        assert stats.counts == {"all-gather": 1, "all-reduce": 1,
                                "reduce-scatter": 1, "collective-permute": 1}
        ag = 64 * 64 * 2 * 15 / 16                 # S_out * (n-1)/n
        ar = 2 * 32 * 4 * 255 / 256                # 2S(n-1)/n
        rs = 8 * 8 * 2 * 16 * 15 / 16              # S_in (n*out) * (n-1)/n
        cp = 4 * 4 * 4
        assert stats.wire_bytes == pytest.approx(ag + ar + rs + cp)

    def test_cross_pod_detection(self):
        hlo = "%ar = f32[8]{0} all-reduce(%y), replica_groups=[1,512]<=[512]"
        stats = parse_collectives(hlo, 512, pod_size=256)
        assert stats.cross_pod_wire_bytes > 0

    def test_start_ops_counted_once(self):
        hlo = "\n".join([
            "%s = bf16[64]{0} all-reduce-start(%x), replica_groups=[1,16]<=[16]",
        ])
        stats = parse_collectives(hlo, 16)
        assert stats.counts == {"all-reduce": 1}

    @given(st.integers(1, 4096), st.integers(2, 256))
    @settings(max_examples=30, deadline=None)
    def test_wire_bytes_nonnegative_and_bounded(self, elems, group):
        hlo = f"%ag = f32[{elems}] all-gather(%x), replica_groups=[1,{group}]<=[{group}]"
        stats = parse_collectives(hlo, group)
        assert 0 <= stats.wire_bytes <= elems * 4


class TestModelFlops:
    def test_train_uses_6nd(self):
        cfg = get_config("yi-6b")
        f = model_flops(cfg, SHAPES["train_4k"])
        assert f == pytest.approx(6.0 * cfg.n_params * 256 * 4096)

    def test_moe_uses_active_params(self):
        cfg = get_config("qwen3-moe-30b-a3b")
        f = model_flops(cfg, SHAPES["train_4k"])
        assert f < 6.0 * cfg.n_params * 256 * 4096   # active << total
        assert f == pytest.approx(6.0 * cfg.n_active_params * 256 * 4096)

    def test_decode_counts_one_token_per_seq(self):
        cfg = get_config("gemma-2b")
        f = model_flops(cfg, SHAPES["decode_32k"])
        assert f == pytest.approx(2.0 * cfg.n_params * 128)


class TestRooflineReport:
    def test_bottleneck_selection(self):
        coll = CollectiveStats(wire_bytes=50e9 * 3)   # 3 s of wire
        r = RooflineReport.build(
            "a", "s", "m", 256,
            {"flops": 197e12 * 1.0, "bytes accessed": 819e9 * 2.0},
            1024, coll, model_flops_total=197e12 * 256 * 0.5,
        )
        assert r.compute_s == pytest.approx(1.0)
        assert r.memory_s == pytest.approx(2.0)
        assert r.collective_s == pytest.approx(3.0)
        assert r.bottleneck == "collective"
        assert r.useful_flops_ratio == pytest.approx(0.5)
        assert r.roofline_fraction == pytest.approx(1.0 / 3.0)


class TestTrainingDriver:
    def test_steered_training_converges(self):
        from repro.launch.train import run
        rep = run(arch="gemma-2b", steps=30, chunk=10, seq=32, batch=4, lr=3e-3)
        assert rep["steps"] >= 30
        assert rep["final_loss"] < rep["first_loss"]

    def test_preemption_recovery(self, tmp_path):
        from repro.launch.train import run
        rep = run(arch="gemma-2b", steps=40, chunk=10, seq=32, batch=4, lr=3e-3,
                  ckpt_dir=str(tmp_path), ckpt_every=10, preempt_at=20)
        assert rep["preempted"]
        assert rep["workers_replaced"] >= 1        # node replaced
        assert rep["final_loss"] < rep["first_loss"]  # and training recovered

    def test_failing_chunk_gives_up(self, monkeypatch):
        """A chunk that always fails ends the run with an error instead of
        resubmitting until the driver's timeout."""
        import repro.launch.train as train

        def broken_chunk(*args, **kwargs):
            raise RuntimeError("no VJP on this backend")

        monkeypatch.setattr(train, "train_chunk", broken_chunk)
        t0 = time.monotonic()
        rep = train.run(arch="gemma-2b", steps=20, chunk=10, seq=32, batch=4)
        assert rep["error"] and "no VJP" in rep["error"]
        assert rep["steps"] == 0
        assert time.monotonic() - t0 < 60


class TestCompileCache:
    def _cache_dir(self, env_value):
        code = ("import jax; from repro.launch.compile_cache import use_compile_cache; "
                "print(use_compile_cache()); print(jax.config.jax_compilation_cache_dir)")
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_value:
            env["JAX_COMPILATION_CACHE_DIR"] = env_value
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, env=env, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return proc.stdout.split()

    def test_env_var_wins(self, tmp_path):
        used, configured = self._cache_dir(str(tmp_path))
        assert used == configured == str(tmp_path)

    def test_default_is_fixed_path_in_checkout(self):
        used, configured = self._cache_dir(None)
        assert used == configured == os.path.join(REPO, ".jax_cache")


class TestReportRendering:
    def test_roofline_table_renders(self, tmp_path):
        from repro.launch.report import load_cells, roofline_table, dryrun_table
        cell = {
            "arch": "yi-6b", "shape": "train_4k", "mesh": "pod256", "status": "ok",
            "compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.5,
            "bottleneck": "memory", "peak_memory_bytes": 2**30,
            "useful_flops_ratio": 0.5, "roofline_fraction": 0.5,
            "compile_s": 1.0, "argument_bytes": 2**29, "temp_bytes": 2**29,
            "collective_counts": {"all-reduce": 3},
        }
        with open(os.path.join(tmp_path, "c.json"), "w") as f:
            json.dump(cell, f)
        cells = load_cells(str(tmp_path))
        table = roofline_table(cells, "pod256")
        assert "yi-6b" in table and "memory" in table
        table2 = dryrun_table(cells)
        assert "all-reduce:3" in table2

"""The harness end to end at a size the CPU holds, without the look for a chip.

A sound run is correct; a run whose timed path is broken underneath (a
token altered where it is produced, a step that hands back its cache
unchanged) is not. A new cell and a new metric need new files and
``BENCHMARK.json`` entries only. Without a TPU, or without the program,
the command exits non-zero and prints no result.
"""

import json
import os
import shutil
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import pytest

import repro.serve.engine as engine_mod
from bench import flops, run
from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[2]
STD = 0.14
SMOKE_LIMITS = {"max_logit_gap": 0.1, "min_tokens_compared": 64}


def cell_named(name: str) -> run.Cell:
    """A cell of ``BENCHMARK.json``, or the open-loop chat mix on phi4-mini,
    which no cell runs yet: built from its files as the harness builds one."""
    if name != "phi4-mini.chat-burst":
        return run.find_cell(name)
    mix = json.loads((ROOT / "bench" / "traffic" / "chat-burst.json").read_text())
    return replace(run.find_cell("phi4-mini.agent-decode"), name=name, mix=mix)


def smoke(cell: run.Cell) -> tuple:
    """The cell at a smoke width, 4 slots, its mix shortened to fit."""
    cfg = smoke_config(cell.config["arch"]).with_(dtype="bfloat16")
    cell.config = dict(cell.config, n_slots=4, max_len=512)
    mix = dict(cell.mix)
    if mix["loop"] == "closed":
        mix.update(clients=4, output=dict(mix["output"], median=40, min=16, max=120))
    else:
        mix["arrivals"] = dict(mix["arrivals"], knee_req_per_s=6.0)
    cell.mix, cell.limits = mix, dict(SMOKE_LIMITS)
    return cell, cfg


def go(cell, cfg, seed=5, trace=False):
    return run.run_cell(cell, seed, 2.0, trace, require_tpu=False, cfg=cfg, std=STD,
                        log=lambda m: None)


@pytest.mark.parametrize("name", ["phi4-mini.agent-decode", "phi4-mini.chat-burst"])
def test_sound_run_is_correct(name):
    cell, cfg = smoke(cell_named(name))
    res = go(cell, cfg)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["checks"]["tokens_compared"]["value"] >= SMOKE_LIMITS["min_tokens_compared"]


def test_traced_run_is_correct_and_reports_per_layer_metrics_only():
    cell, cfg = smoke(cell_named("phi4-mini.chat-burst"))
    res = go(cell, cfg, trace=True)
    assert res["correct"], res["checks"]
    # a CPU run's trace holds no TPU plane: the device readers find nothing
    # and stay silent, and no end-to-end metric is reported
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}


def broken_step(fault):
    orig = engine_mod.make_serve_step

    def make(model, **kw):
        step = orig(model, **kw)

        def serve_step(params, cache, tokens, lengths, rng):
            nxt, finite, new_cache = step(params, cache, tokens, lengths, rng)
            if fault == "token":
                nxt = (nxt + 1) % model.cfg.vocab_size
            if fault == "state":
                new_cache = cache
            return nxt, finite, new_cache

        return serve_step

    return make


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(engine_mod, "make_serve_step", broken_step(fault))
    cell, cfg = smoke(run.find_cell("yi-6b.agent-decode"))
    res = go(cell, cfg, seed=9)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > SMOKE_LIMITS["max_logit_gap"]
    assert res["failed"] > 0


def test_control_in_the_programs_place_is_not_correct():
    """``--control 1``: the float8 control's gaps go through the same checks."""
    cell, cfg = smoke(run.find_cell("phi4-mini.agent-decode"))
    res = run.run_cell(cell, 7, 2.0, False, require_tpu=False, cfg=cfg, std=STD, control=True,
                       log=lambda m: None)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > SMOKE_LIMITS["max_logit_gap"]
    assert res["failed"] > 0


def test_sweep_window_reads_the_cell_metrics():
    from bench import sweep, weights
    from repro.models import build_model

    cell, cfg = smoke(cell_named("phi4-mini.chat-burst"))
    model = build_model(cfg)
    params = weights.make(model.shapes(), 3, STD)
    mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate_req_per_s=2.0))
    row = sweep.window(cell.config, model, params, mix, 3, 2.0, run.model_dict(cfg, cell.config))
    assert row["rate"] == 2.0 and row["schedule"] == cell.mix["trace_seed"]
    assert row["offered_req_per_s"] > 0 and row["tokens_per_s"] > 0
    assert row["ttft_p95_s"] > 0 and isinstance(row["sustained"], bool)


def test_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "steady-chat.json").write_text(json.dumps({
        "loop": "open", "arrivals": {"dist": "gamma", "cv": 1.0, "rate_req_per_s": 4.0},
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 64},
        "output": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 64},
        "trace_seed": 7, "preroll": {"schedule_s": 0.5}}))
    (tmp_path / "bench" / "limits" / "yi-6b.steady-chat.json").write_text(
        json.dumps(SMOKE_LIMITS))
    (tmp_path / "bench" / "metrics" / "requests_finished_per_s.py").write_text(
        "def read(run):\n"
        "    return sum(run.t_open <= t < run.t_close for t in run.rec.finished.values())"
        " / run.seconds\n")
    spec["workloads"].append({"name": "yi-6b.steady-chat", "config": "yi-6b",
                              "traffic": "steady-chat", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "requests_finished_per_s", "unit": "req/s",
                               "better": "higher", "bound": 0.05, "source": "host_clock",
                               "workloads": ["yi-6b.steady-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.find_cell("yi-6b.steady-chat", root=tmp_path)
    assert cell.mix["trace_seed"] == 7 and cell.root == tmp_path
    cell.config = dict(cell.config, n_slots=4, max_len=256)
    res = go(cell, smoke_config("yi-6b").with_(dtype="bfloat16"))
    assert res["correct"], res["checks"]
    assert res["metrics"]["requests_finished_per_s"]["value"] > 0
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(res["metrics"])


OTHER_MODULE = '''"""The dense family under another name; its last layer attends half the length."""
from bench.reference import dense

CALLS = {"served_gaps": 0, "token_flops": 0, "attended": 0}


def served_gaps(*args, **kw):
    CALLS["served_gaps"] += 1
    return dense.served_gaps(*args, **kw)


def token_flops(cfg, context, served):
    CALLS["token_flops"] += 1
    return dense.token_flops(cfg, context, served)


def attended(cfg, length):
    CALLS["attended"] += 1
    return dense.attended(cfg, length)[:-1] + [length // 2]
'''


def test_new_configuration_is_files_and_entries_only(tmp_path):
    """A configuration of another family: its model module, its file with a
    key that the dense files lack, and a cell, all new files and entries."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "reference" / "other.py").write_text(OTHER_MODULE)
    config = json.loads((ROOT / "bench" / "configs" / "yi-6b.json").read_text())
    config["reference"] = "other"
    config["model"]["local_window"] = 0
    (tmp_path / "bench" / "configs" / "other-6b.json").write_text(json.dumps(config))
    (tmp_path / "bench" / "limits" / "other-6b.agent-decode.json").write_text(
        json.dumps(SMOKE_LIMITS))
    (tmp_path / "bench" / "metrics" / "attended_at_64.py").write_text(
        "def read(run):\n    return sum(run.reference.attended(run.model, 64))\n")
    (tmp_path / "bench" / "metrics" / "flops_at_64.py").write_text(
        "def read(run):\n    return run.reference.token_flops(run.model, 64, served=True)\n")
    spec["configs"].append({"name": "other-6b", "source": "https://huggingface.co/01-ai/Yi-6B",
                            "file": "bench/configs/other-6b.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other-6b.agent-decode", "config": "other-6b",
                              "traffic": "agent-decode", "chips": 1, "why": "test"})
    for name in ("attended_at_64", "flops_at_64"):
        spec["end_to_end"].append({"name": name, "unit": "count", "better": "higher",
                                   "bound": 0.05, "source": "host_clock",
                                   "workloads": ["other-6b.agent-decode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell, cfg = smoke(run.find_cell("other-6b.agent-decode", root=tmp_path))
    res = go(cell, cfg)
    assert res["correct"], res["checks"]
    other = run.reference_module("other", tmp_path)
    assert other.CALLS == {"served_gaps": other.CALLS["served_gaps"], "token_flops": 1,
                           "attended": 1}
    assert other.CALLS["served_gaps"] > 0
    # the readers get the cell's module and the file's whole model, at the
    # program's smoke sizes: two layers, the second at half the length
    model = run.model_dict(cfg, cell.config)
    assert model["local_window"] == 0 and list(model) == list(config["model"])
    assert res["metrics"]["attended_at_64"]["value"] == 64 + 32
    assert res["metrics"]["flops_at_64"]["value"] == flops.token_flops(model, 64, served=True)
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(res["metrics"])


def test_configuration_without_a_model_module_is_an_error(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    path = tmp_path / "bench" / "configs" / "yi-6b.json"
    config = json.loads(path.read_text())
    del config["reference"]
    path.write_text(json.dumps(config))
    with pytest.raises(KeyError, match="reference"):
        run.find_cell("yi-6b.agent-decode", root=tmp_path)
    assert run.find_cell("phi4-mini.agent-decode", root=tmp_path).config["reference"] == "dense"


def command(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "yi-6b.agent-decode",
                           "--seed", "3", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    p = command(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_benchmark_alone_means_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path)
    p = command(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""The Mellum2 cell (``mellum2.agent-decode``): its configuration file, its
model module ``bench/reference/moe_window.py`` against the program at a
smoke size on the CPU, the module's counts written out by hand at the
published widths, and the two readers of the expert layer."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import peaks, run, spans, weights
from bench.kernels import moe_experts
from bench.reference import moe_window

from repro.configs import smoke_config
from repro.models import build_model
from repro.serve import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[2]
CELL = "mellum2.agent-decode"
CONFIG = json.loads((ROOT / "bench" / "configs" / "mellum2-12b-a2.5b.json").read_text())
ARCH = CONFIG["arch"]
STD = 0.14          # the dense tests' smoke scale: N(0, 0.02) at width 3072 is N(0, 0.14) at 64
# A float32 program and the float32 reference differ in the order of their
# sums alone, about 1e-6 of a logit; one routing decision taken otherwise
# moves a logit by 0.01 or more at this size.
F32_LIMIT = 1e-4
# The float8 control's widest gap read 1.9-4.0 over ``serve``'s seeds 1-4 (the
# smoke width routes 2 of 8 experts, so a rounding flips a whole expert);
# a served token changed by hand reads a gap of the same order.
CONTROL_ABOVE = 0.1


def sizes(cfg):
    """The model dict the harness hands the module for ``cfg``."""
    return run.model_dict(cfg, CONFIG)


def test_cell_loads_with_its_twelve_per_layer_metrics():
    cell = run.find_cell(CELL)
    assert cell.config["reference"] == "moe_window" and cell.chips == 1
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 12 and names[-2:] == ["moe.experts_roofline", "model.moe_ms"]
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    cfg = run.model_config(cell.config)
    assert (cfg.n_experts, cfg.router_width, cfg.full_every, cfg.local_window) == (16, 64, 4, 1024)
    assert run.reference_module("moe_window", ROOT).attended is not None


def test_file_states_the_catalog_config_with_the_held_experts_cut():
    """Every key of the published config at the file's top level, as run:
    the experts held here are the one key changed, and it is in
    ``reduced``; the published count and the deployment stand beside it."""
    published = CONFIG["published"]
    changed = sorted(k for k in published if CONFIG[k] != published[k])
    assert changed == CONFIG["reduced"] == ["num_experts"]
    assert (CONFIG["num_experts"], published["num_experts"]) == (16, 64)
    assert "4-way expert parallelism" in CONFIG["deployment"]
    model = CONFIG["model"]
    assert model["n_experts"] == CONFIG["num_experts"]
    assert model["router_experts"] == published["num_experts"]
    assert model["local_window"] == published["sliding_window"]
    full = published["layer_types"]
    assert [t == "full_attention" for t in full] == [(i + 1) % model["full_every"] == 0
                                                    for i in range(model["n_layers"])]
    yarn = published["rope_parameters"]["full_attention"]
    assert (model["yarn_factor"], model["yarn_original_len"], model["yarn_attention_factor"]) == (
        yarn["factor"], yarn["original_max_position_embeddings"], yarn["attention_factor"])


def test_counts_by_hand_at_the_published_widths():
    """Per layer: q, k, v, o hold 2304 x 4096 + 2 x 2304 x 512 + 4096 x 2304
    = 21,233,664 weights, the router 2304 x 64 = 147,456, and the 2 routed
    experts a token runs on this chip (8 x 16 / 64) 2 x 3 x 2304 x 896 =
    12,386,304; attention costs 4 x 32 x 128 = 16,384 per position attended.
    Of every four layers three attend min(n, 1024), the fourth n."""
    model = CONFIG["model"]
    assert moe_window.attended(model, 700) == [700] * 28
    assert moe_window.attended(model, 1500) == [1024, 1024, 1024, 1500] * 7
    weights_ = 21233664 + 147456 + 12386304
    assert moe_window.token_flops(model, 1500, served=False) == \
        28 * 2 * weights_ + 16384 * (21 * 1024 + 7 * 1500)
    assert moe_window.token_flops(model, 100, served=True) == \
        28 * 2 * weights_ + 16384 * 28 * 100 + 2 * 2304 * 98304


def test_reference_yarn_and_window_follow_transformers():
    model = CONFIG["model"]
    # transformers 4.57.6 ``_compute_yarn_parameters`` at the full layers,
    # float32 (so 1e-6 relative): inverse frequencies 0, 8, 16, 35 and 63
    want = {0: 1.0, 8: 0.193922758102417, 16: 0.03760603070259094, 35: 4.778106085723266e-05,
            63: 1.5344629389346665e-07}
    got = moe_window.yarn_inv_freq(model)
    np.testing.assert_allclose([got[i] for i in want], list(want.values()), rtol=1e-6)

    def sliding_window_overlay(q_idx, kv_idx, window):   # its inner_mask, over a causal mask
        return kv_idx <= q_idx and kv_idx > q_idx - window

    for window in (3, 16):
        mask = np.asarray(moe_window.window_mask(40, window))
        assert all(mask[q, kv] == sliding_window_overlay(q, kv, window)
                   for q in range(40) for kv in range(40))
        assert mask.sum(1).max() == window          # the query's own position included
    assert np.asarray(moe_window.window_mask(40, 0)).sum() == 40 * 41 // 2


def test_reference_matches_the_program_forward_in_float32():
    cfg = smoke_config(ARCH).with_(dtype="float32", capacity_factor=64.0)   # no capacity drops
    model = build_model(cfg)
    params = weights.make(model.shapes(), 3, STD)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, 60).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
        h = moe_window.hidden(sizes(cfg), params, tokens)[: len(tokens)]
        got = np.asarray(h @ params["unembed"][: cfg.vocab_size].T)
    want = np.asarray(want[0, :, : cfg.vocab_size])
    # float32 both sides: the order of sums, 1e-6 of the largest logit
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def serve(seed, dtype, n_new=40):
    cfg = smoke_config(ARCH).with_(dtype=dtype)
    model = build_model(cfg)
    params = weights.make(model.shapes(), seed, STD)
    done = []
    engine = ServingEngine(model, params, n_slots=2, max_len=128, on_finish=done.append)
    rng = np.random.default_rng(seed)
    for i in range(3):
        prompt = rng.integers(1, cfg.vocab_size, int(rng.integers(20, 30))).astype(np.int32)
        engine.submit(Request(i, prompt, max_new_tokens=n_new))
    engine.run_until_drained()
    return cfg, params, done


@pytest.mark.parametrize("seed", [1, 2])
def test_engine_prefill_then_decode_matches_the_reference(seed):
    """Prompts of 20-29 fed token by token, then 40 tokens decoded: every
    sequence passes 48 positions, so each window-16 ring wraps twice. The
    served tokens' gaps under the reference stay within float32 rounding,
    and the float8 control's do not."""
    cfg, params, done = serve(seed, "float32")
    assert len(done) == 3 and min(len(r.prompt) + len(r.generated) for r in done) > 3 * 16
    served, control = [], []
    for req in done:
        out = moe_window.served_gaps(sizes(cfg), params, req.prompt,
                                     np.asarray(req.generated, np.int32), control=True)
        assert out["served"].shape == (len(req.generated),)
        served.append(out["served"].max())
        control.append(out["control"].max())
    assert max(served) <= F32_LIMIT
    assert max(control) > CONTROL_ABOVE


def test_a_wrong_token_reads_a_wide_gap():
    cfg, params, done = serve(4, "float32", n_new=12)
    req = done[0]
    bad = np.asarray(req.generated, np.int32).copy()
    bad[5] = (bad[5] + 1) % cfg.vocab_size
    out = moe_window.served_gaps(sizes(cfg), params, req.prompt, bad)
    assert out["served"][5] > CONTROL_ABOVE


def test_smoke_run_of_the_cell_is_correct():
    """``run_cell`` at the smoke size, float32 (see F32_LIMIT), 4 slots."""
    cell = run.find_cell(CELL)
    cfg = smoke_config(ARCH).with_(dtype="float32")
    cell.config = dict(cell.config, n_slots=4, max_len=512)
    cell.mix = dict(cell.mix, clients=4, output=dict(cell.mix["output"], median=40, min=16, max=120))
    cell.limits = {"max_logit_gap": F32_LIMIT, "min_tokens_compared": 64}
    res = run.run_cell(cell, 5, 2.0, False, require_tpu=False, cfg=cfg, std=STD, log=lambda m: None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}


# --- the expert layer's readers on a synthetic trace
def scoped(name, scope):
    return (f'  %{name} = bf16[8]{{0}} fusion(%a), kind=kOutput, metadata={{op_name='
            f'"jit(serve_step)/layers/while/body/closed_call/mlp/{scope}/dot_general"}}')


HLO = "\n".join(["HloModule jit_serve_step", "ENTRY %main {",
                 scoped("fusion.1", "moe.router"), scoped("fusion.2", "moe.experts"),
                 scoped("fusion.3", "moe.experts"), scoped("fusion.4", "attn"), "}"])
D, F = 2304, 896


def moe_run(experts_s, calls=10, decode=8, touched=700, pairs=900, hlo=HLO):
    layers = spans.Layers(window_s=1.0, span_s={}, gaps=[], idle_by_span={},
                          program_calls={"jit_serve_step": calls},
                          program_op_s={"jit_serve_step": {"fusion.1": 0.001, "fusion.2": experts_s / 2,
                                                           "fusion.3": experts_s / 2, "fusion.4": 0.5}})
    stats = lambda n, t, p: SimpleNamespace(steps=n, experts_touched=t, expert_pairs=p)
    return SimpleNamespace(layers=layers, hlo_text=hlo, peaks=peaks.peaks("TPU v5 lite"),
                           model=CONFIG["model"], stats_open=stats(100, 5, 7),
                           stats_close=stats(100 + decode, 5 + touched, 7 + pairs))


def least_s(touched, pairs):
    p = peaks.peaks("TPU v5 lite")
    ops, nbytes = moe_experts.cost(pairs, touched, D, F)
    assert (ops, nbytes) == (2 * 3 * D * F * pairs, 2 * 3 * D * F * touched)
    return max(ops / p["flops_per_s"]["bfloat16"], nbytes / p["hbm_bytes_per_s"])


@pytest.mark.parametrize("slowdown", [1.0, 1.6, 25.0])
def test_roofline_reaches_100_only_when_the_touched_experts_alone_are_timed(slowdown):
    # every call decodes: the scope's time is all the counters' work
    least = least_s(700, 900)
    r = moe_run(least * slowdown, calls=8, decode=8)
    share = run.reader("moe.experts_roofline")(r)
    assert share == pytest.approx(100 / slowdown) and share <= 100 + 1e-9
    # two prefill calls of ten: the decode calls' part of the scope's time
    r = moe_run(least * slowdown * 10 / 8, calls=10, decode=8)
    assert run.reader("moe.experts_roofline")(r) == pytest.approx(100 / slowdown)


def test_moe_ms_is_the_expert_scopes_per_call():
    r = moe_run(0.2, calls=10)
    assert run.reader("model.moe_ms")(r) == pytest.approx(1e3 * (0.001 + 0.2) / 10)


def test_expert_readers_are_silent_on_a_dense_or_parent_program():
    dense_hlo = HLO.replace("moe.experts", "x").replace("moe.router", "y")
    r = moe_run(0.2, hlo=dense_hlo)
    assert run.reader("model.moe_ms")(r) is None
    assert run.reader("moe.experts_roofline")(r) is None
    r = moe_run(0.2)
    r.stats_open = r.stats_close = SimpleNamespace(steps=5)       # a parent's EngineStats
    assert run.reader("moe.experts_roofline")(r) is None
    r.layers = None
    assert run.reader("model.moe_ms")(r) is None

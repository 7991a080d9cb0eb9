"""Operations, bytes and peaks: by hand for one shape; no share above 100%.
The readers take the counts from the cell's model module: the dense
module's give the readings of the formulas they replaced, to the last
digit, and a hand-written module of another family is charged as the
convention of ``bench/reference/__init__.py`` says."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, peaks, run, trace
from bench.kernels import decode_attention
from bench.reference import dense

ROOT = Path(__file__).resolve().parents[2]
PHI4 = {"n_layers": 32, "d_model": 3072, "n_heads": 24, "n_kv_heads": 8, "head_dim": 128,
        "d_ff": 8192, "vocab_size": 200064}


def test_decode_attention_cost_by_hand():
    ops, nbytes = decode_attention.cost([100, 2048], n_heads=24, n_kv_heads=8, head_dim=128)
    # q.K and p.V: 2 products x 2 ops x 24 heads x 128 dims per position attended
    assert ops == 4 * 24 * 128 * (100 + 2048)
    # K and V at the valid length, 8 kv heads x 128 dims, bf16; q and out, 24 x 128
    assert nbytes == 2 * (2 * 8 * 128 * (100 + 2048)) + 2 * 2 * (2 * 24 * 128)
    assert decode_attention.cost([], 24, 8, 128) == (0, 0)


def test_decode_attention_is_bound_by_bytes():
    ops, nbytes = decode_attention.cost([1024] * 8, 24, 8, 128)
    p = peaks.peaks("TPU v5 lite")
    assert nbytes / p["hbm_bytes_per_s"] > ops / p["flops_per_s"]["bfloat16"]


def test_token_flops_by_hand():
    per_layer_w = 3072 * 24 * 128 + 2 * 3072 * 8 * 128 + 24 * 128 * 3072 + 3 * 3072 * 8192
    assert flops.layer_matmul_weights(PHI4) == per_layer_w
    ctx = 500
    want = 32 * (2 * per_layer_w + 4 * 24 * 128 * ctx)
    assert flops.token_flops(PHI4, ctx, served=False) == want
    assert flops.token_flops(PHI4, ctx, served=True) == want + 2 * 3072 * 200064


def test_peaks_of_a_v5e_and_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"]["bfloat16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def fake_run(kernel_s, window_s, step_calls=5):
    """Two ``step()`` calls: the first admits a prompt of 4 (3 prefill calls)."""
    steps = [run.StepCall(0.0, 1.0, prefill=[1, 2, 3], decode=[40, 700, 2048]),
             run.StepCall(1.0, 2.0, prefill=[], decode=[41, 701])]
    reduced = trace.Reduced(window_s=window_s, busy_s=window_s, op_self_s={
        "decode_attention.4": kernel_s, "fusion.1": 1.0}, modules={
        "jit_serve_step": (step_calls, 1.0)}, op_calls={"decode_attention.4": 32 * step_calls})
    return SimpleNamespace(model=PHI4, reference=dense, peaks=peaks.peaks("TPU v5 lite"), trace=reduced,
                           window_steps=lambda: steps, stats_open=SimpleNamespace(steps=10),
                           stats_close=SimpleNamespace(steps=12))


def least_times(r):
    p = r.peaks
    att = mm = 0.0
    for s in r.window_steps():
        for lengths in [[n] for n in s.prefill] + [s.decode]:
            o, b = decode_attention.cost(lengths, 24, 8, 128)
            att += max(o / p["flops_per_s"]["bfloat16"], b / p["hbm_bytes_per_s"])
        mm += sum(flops.token_flops(PHI4, n, False) for n in s.prefill)
        mm += sum(flops.token_flops(PHI4, n, True) for n in s.decode)
    return 32 * att, mm / p["flops_per_s"]["bfloat16"]


@pytest.mark.parametrize("slowdown", [1.0, 1.5, 40.0])
def test_shares_reach_100_only_at_the_least_time(slowdown):
    att, mm = least_times(fake_run(1.0, 1.0))
    r = fake_run(att * slowdown, mm * slowdown)
    roof = run.reader("decode_attention_roofline")(r)
    mfu = run.reader("step.mfu")(r)
    assert roof == pytest.approx(100 / slowdown) and mfu == pytest.approx(100 / slowdown)
    assert roof <= 100 + 1e-9 and mfu <= 100 + 1e-9


def test_roofline_charges_only_the_calls_the_trace_shows():
    """A prefill that no longer calls the kernel once per prompt token (the
    trace shows the two decode calls alone) is not charged for those calls."""
    att, _ = least_times(fake_run(1.0, 1.0))
    full = run.reader("decode_attention_roofline")(fake_run(att, 1.0))
    fewer = run.reader("decode_attention_roofline")(fake_run(att, 1.0, step_calls=2))
    assert full == pytest.approx(100)
    assert fewer < full
    p = peaks.peaks("TPU v5 lite")
    decode_only = sum(decode_attention.cost(x, 24, 8, 128)[1] for x in ([40, 700, 2048], [41, 701]))
    assert fewer == pytest.approx(100 * 32 * decode_only / p["hbm_bytes_per_s"] / att)


@pytest.mark.parametrize("step_calls,share", [(5, 60.0), (2, 0.0)])
def test_prefill_call_share_counts_the_program_calls(step_calls, share):
    # two decode steps by EngineStats; the rest of the traced calls prefilled
    r = fake_run(1.0, 1.0, step_calls=step_calls)
    assert run.reader("engine.prefill_call_share")(r) == pytest.approx(share)


def test_readers_stay_silent_without_a_trace():
    r = fake_run(1.0, 1.0)
    r.trace = None
    for name in ("decode_attention_roofline", "step.mfu", "step.device_ms", "device.idle_share",
                 "engine.prefill_call_share"):
        assert run.reader(name)(r) is None


def test_dense_module_counts():
    assert dense.token_flops is flops.token_flops
    assert dense.attended(PHI4, 700) == [700] * 32


# --- the formulas of the two readers before they took the module's counts
def mfu_before(run):
    if run.trace is None or run.peaks is None:
        return None
    cfg, total = run.model, 0
    for s in run.window_steps():
        total += sum(flops.token_flops(cfg, n, served=False) for n in s.prefill)
        total += sum(flops.token_flops(cfg, n, served=True) for n in s.decode)
    peak = run.peaks["flops_per_s"]["bfloat16"]
    return 100.0 * total / (run.trace.window_s * peak) if total else None


def roofline_before(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s(decode_attention.NAME)
    cfg = run.model
    calls = run.trace.kernel_calls(decode_attention.NAME) // cfg["n_layers"]
    if not t or not calls:
        return None
    steps = run.window_steps()
    decode = [s.decode for s in steps if s.decode][:calls]
    prefill = sorted(n for s in steps for n in s.prefill)[:calls - len(decode)]
    least = 0.0
    for lengths in decode + [[n] for n in prefill]:
        ops, nbytes = decode_attention.cost(lengths, cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"])
        least += max(ops / run.peaks["flops_per_s"]["bfloat16"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["n_layers"] * least / t if least else None


def model_of(arch):
    return json.loads((ROOT / "bench" / "configs" / f"{arch}.json").read_text())["model"]


def seeded_steps(seed, n_steps, slots=8, max_len=2048):
    rng = np.random.default_rng(seed)
    steps = []
    for i in range(n_steps):
        prefill = sorted(int(x) for x in rng.integers(1, 128, int(rng.integers(0, 3)) * 20))
        decode = [int(x) for x in rng.integers(1, max_len, int(rng.integers(1, slots + 1)))]
        steps.append(run.StepCall(float(i), i + 1.0, prefill=prefill, decode=decode))
    return steps


def recorded_or_seeded(case, arch):
    """A run of the dense module: a trace recorded on the chip with steps to
    match its calls, or a synthetic trace over seeded steps."""
    model = model_of(arch)
    if case.startswith("testdata/"):
        reduced = trace.load(str(ROOT / "bench" / case))
        calls = reduced.kernel_calls(decode_attention.NAME) // model["n_layers"]
        steps = seeded_steps(calls, calls)
    else:
        steps = seeded_steps(int(case), 300)
        n_calls = len(steps) + sum(len(s.prefill) for s in steps)
        rng = np.random.default_rng(int(case) + 1)
        reduced = trace.Reduced(window_s=float(rng.uniform(1, 60)), busy_s=1.0,
                                op_self_s={"decode_attention.4": float(rng.uniform(0.1, 5))},
                                modules={"jit_serve_step": (n_calls, 1.0)},
                                op_calls={"decode_attention.4": model["n_layers"] * n_calls})
    return run.Run(model=model, reference=dense, seconds=1.0, t_open=0.0, t_close=1e9,
                   setup_s=1.0, rec=run.Record(steps=steps), stats_open=None, stats_close=None,
                   peak_bytes=None, peaks=peaks.peaks("TPU v5 lite"), trace=reduced)


@pytest.mark.parametrize("case,arch", [("testdata/phi4_steps", "phi4-mini-3.8b"),
                                       ("testdata/phi4_admit", "phi4-mini-3.8b"),
                                       ("1", "phi4-mini-3.8b"), ("2", "yi-6b"), ("3", "yi-6b")])
def test_dense_readings_equal_the_formulas_before(case, arch):
    r = recorded_or_seeded(case, arch)
    mfu, roof = run.reader("step.mfu")(r), run.reader("decode_attention_roofline")(r)
    assert mfu is not None and roof is not None
    assert mfu == mfu_before(r)
    assert roof == roofline_before(r)


# --- a family the harness has no module for yet, written out by hand: 64
# wide experts routed top-2 of 8 with one shared expert, three sliding
# layers of window 100 then one full layer; ``held`` experts on this chip
MOE = {"n_layers": 4, "d_model": 256, "n_heads": 8, "n_kv_heads": 2, "head_dim": 32,
       "d_ff": 128, "vocab_size": 1000, "n_experts": 8, "experts_per_token": 2,
       "n_shared_experts": 1, "window": 100, "full_every": 4}


def moe_module(held):
    def attended(cfg, length):
        return [length if (i + 1) % cfg["full_every"] == 0 else min(length, cfg["window"])
                for i in range(cfg["n_layers"])]

    def token_flops(cfg, context, served):
        d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
        experts = cfg["experts_per_token"] * held / cfg["n_experts"] + cfg["n_shared_experts"]
        weights = d * h * hd + 2 * d * kv * hd + h * hd * d + d * cfg["n_experts"] \
            + experts * 3 * d * cfg["d_ff"]
        attention = sum(4 * h * hd * n for n in attended(cfg, context))
        unembed = 2 * d * cfg["vocab_size"] if served else 0
        return cfg["n_layers"] * 2 * weights + attention + unembed

    return SimpleNamespace(attended=attended, token_flops=token_flops)


@pytest.mark.parametrize("held,experts", [(8, 3), (2, 1.5)])
def test_counts_of_another_family_by_hand(held, experts):
    """The readers count such a module's layers as it states them. Worked
    numbers of the convention: ``attended(MOE, 40)`` is ``[40] * 4`` and
    ``attended(MOE, 700)`` is ``[100, 100, 100, 700]``; per layer q, k, v,
    o hold 163,840 weights, the router 256 x 8, each expert 3 x 256 x 128,
    and attention costs 4 x 8 x 32 = 1,024 per position attended, so
    ``token_flops(MOE, 700, served=False)`` is ``4 x 2 x weights + 1024 x
    (3 x 100 + 700)``, and a served token adds the unembedding, 512,000."""
    mod = moe_module(held)
    weights = 163840 + 2048 + experts * 98304
    p = peaks.peaks("TPU v5 lite")
    steps = [run.StepCall(0.0, 1.0, prefill=[1, 2, 3], decode=[40, 700])]
    kernel_s = 1e-3
    reduced = trace.Reduced(window_s=2.0, busy_s=2.0, op_self_s={"decode_attention.4": kernel_s},
                            modules={"jit_serve_step": (4, 1.0)},
                            op_calls={"decode_attention.4": 4 * 4})
    r = SimpleNamespace(model=MOE, reference=mod, peaks=p, trace=reduced, window_steps=lambda: steps)
    # prefill tokens at contexts 1, 2, 3 (every layer attends all); decode
    # tokens at 40 (all layers) and 700 (three windows of 100, one full)
    total = 5 * 4 * 2 * weights + 1024 * (4 * (1 + 2 + 3) + 4 * 40 + 3 * 100 + 700) + 2 * 512000
    assert run.reader("step.mfu")(r) == pytest.approx(100 * total / (2.0 * p["flops_per_s"]["bfloat16"]))
    # the decode call: three sliding layers read [40, 100] positions, the
    # full one [40, 700]; the three prefill calls read [1], [2], [3] in
    # every layer. K and V: 2 x 2 heads x 32 x 2 bytes, 256 a position;
    # q and out: 2 x 8 heads x 32 x 2 bytes, 1,024 a call.
    nbytes = 3 * 2 * (128 * 140 + 2 * 512) + 2 * (128 * 740 + 2 * 512) \
        + 4 * sum(2 * (128 * n + 512) for n in (1, 2, 3))
    assert run.reader("decode_attention_roofline")(r) == pytest.approx(
        100 * nbytes / p["hbm_bytes_per_s"] / kernel_s)

"""Operations, bytes and peaks: by hand for one shape; no share above 100%."""

from types import SimpleNamespace

import pytest

from bench import flops, peaks, run, trace
from bench.kernels import decode_attention

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
    return SimpleNamespace(model=PHI4, peaks=peaks.peaks("TPU v5 lite"), trace=reduced,
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

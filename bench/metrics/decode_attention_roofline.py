"""Share of its roofline that the Pallas decode-attention kernel reaches in
the traced window.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s, summed over the calls that the trace shows, with
``bench/kernels/decode_attention.py``'s count of what each call needs: the
useful slots at their valid lengths. The kernel runs once per layer in each
serve-step call. A decode call serves the slots that got a token in it, at
the lengths the benchmark's hooks saw. The calls beyond those fed a prompt
token to one slot; each is charged at the shortest prompt positions the
window's admissions had, so the least time stays a floor whatever the
engine's prefill does. The bytes bound it. The kernel's time is the device
time of its calls in the trace."""

from bench.kernels import decode_attention as kernel


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s(kernel.NAME)
    cfg = run.model
    calls = run.trace.kernel_calls(kernel.NAME) // cfg["n_layers"]
    if not t or not calls:
        return None
    steps = run.window_steps()
    decode = [s.decode for s in steps if s.decode][:calls]
    prefill = sorted(n for s in steps for n in s.prefill)[:calls - len(decode)]
    least = 0.0
    for lengths in decode + [[n] for n in prefill]:
        ops, nbytes = kernel.cost(lengths, cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"])
        least += max(ops / run.peaks["flops_per_s"]["bfloat16"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * cfg["n_layers"] * least / t if least else None

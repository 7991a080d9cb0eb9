"""Model operations of the tokens processed in the traced window, over the
window's length times the chip's peak (bf16).

The tokens are every prompt token of the requests admitted in the window
and the decode tokens of active slots, as the benchmark's hooks saw them;
their operations are the configuration's model module's ``token_flops``
(``bench/reference/``). That is the model's work, whatever calls the
engine makes for it: a batched prefill needs the same operations. Tokens
that the step computes for idle slots, or for the slots that wait while
another prefills, are not needed and do not count."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    cfg, token_flops, total = run.model, run.reference.token_flops, 0
    for s in run.window_steps():
        total += sum(token_flops(cfg, n, served=False) for n in s.prefill)
        total += sum(token_flops(cfg, n, served=True) for n in s.decode)
    peak = run.peaks["flops_per_s"]["bfloat16"]
    return 100.0 * total / (run.trace.window_s * peak) if total else None

"""Share of its roofline that the expert layer's held experts reach in the
traced window: the least time of the work the program's counters say was
needed (``bench/kernels/moe_experts.py``: every touched held expert read
once per layer call, three products per (token, held expert) pair) at the
chip's peaks, over the device time of the operations in the named scope
``moe.experts``.

The counters come from the decode steps (``EngineStats.experts_touched``
and ``expert_pairs``, fetched with each step's tokens), while the trace
also holds the serve-step calls that fed prompt tokens. Each call runs the
expert layer over the same eight slots, so the scope's time is charged to
the decode calls in proportion: its time times decode calls over all
calls. A program without the scope or the counters reads None."""

from bench import scopes
from bench.kernels import moe_experts as kernel

MODULE = "jit_serve_step"
NAMES = scopes.SCOPES + ("moe.router", "moe.experts")


def read(run):
    layers, hlo = getattr(run, "layers", None), getattr(run, "hlo_text", None)
    if layers is None or hlo is None or run.peaks is None:
        return None
    touched = getattr(run.stats_close, "experts_touched", 0) - getattr(run.stats_open, "experts_touched", 0)
    pairs = getattr(run.stats_close, "expert_pairs", 0) - getattr(run.stats_open, "expert_pairs", 0)
    calls = layers.program_calls.get(MODULE, 0)
    decode = run.stats_close.steps - run.stats_open.steps
    sec = scopes.by_scope(layers.program_op_s.get(MODULE, {}),
                          scopes.op_places(hlo, NAMES)).get(kernel.SCOPE, 0.0)
    if not touched or not calls or not decode or not sec:
        return None
    ops, nbytes = kernel.cost(pairs, touched, run.model["d_model"], run.model["d_ff"])
    least = max(ops / run.peaks["flops_per_s"]["bfloat16"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sec * decode / calls)

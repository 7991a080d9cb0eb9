"""Share of the window's serve-step calls that prefill rather than decode.

The calls are the executions of the serve-step program in the trace (the
module that ``ServingEngine`` jits from ``serve/decode.py``'s
``serve_step``); the decode calls are the change of ``EngineStats.steps``
over the window, one per ``step()`` that served a token. Every other
execution fed a prompt token. Today the engine feeds a prompt of P tokens
through P - 1 such calls while every other slot waits; a prefill that goes
through another program, or batches its tokens, shows as fewer calls here."""

MODULE = "jit_serve_step"


def read(run):
    if run.trace is None:
        return None
    calls, _ = run.trace.module(MODULE)
    decode = run.stats_close.steps - run.stats_open.steps
    return 100.0 * (calls - decode) / calls if calls else None

"""The host's serial work per decode step while the device waits, in ms:
the mean over the window's decode steps of the engine's ``serve.dispatch``
and ``serve.bookkeeping`` spans (hooks included), one of each per step.

It reads ``run.layers`` (``bench/spans.py``), which a traced run has when
the harness reads the engine's spans; a program without them reads None."""


def read(run):
    layers = getattr(run, "layers", None)
    if layers is None:
        return None
    n, dispatch = layers.span_s.get("serve.dispatch", (0, 0.0))
    _, bookkeeping = layers.span_s.get("serve.bookkeeping", (0, 0.0))
    return 1e3 * (dispatch + bookkeeping) / n if n else None

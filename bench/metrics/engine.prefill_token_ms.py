"""Host time per prompt token fed to the cache, in ms: the window's
``serve.prefill`` spans over the change of ``EngineStats.prefill_tokens``.

Near the dispatch cost where the prefill calls queue behind one another,
near a step's device time where each waits for the last. It reads
``run.layers`` (``bench/spans.py``) and the engine's counter; a program
without them reads None."""


def read(run):
    layers = getattr(run, "layers", None)
    tokens = [getattr(s, "prefill_tokens", None) for s in (run.stats_open, run.stats_close)]
    if layers is None or None in tokens or tokens[1] <= tokens[0]:
        return None
    _, sec = layers.span_s.get("serve.prefill", (0, 0.0))
    return 1e3 * sec / (tokens[1] - tokens[0]) if sec else None

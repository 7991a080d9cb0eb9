"""Device time of the KV cache's write path per execution of the serve
step, in ms: its operations in the named scope ``kv_cache.update``, and the
scan's ``dynamic_update_slice`` of each layer's cache into its stacked
output, which falls in ``layers`` (``bench/scopes.py``).

It reads the step's optimized HLO text (``run.hlo_text``) and the device
time by program (``run.layers``); a program without the scopes reads None."""

from bench import scopes

MODULE = "jit_serve_step"


def read(run):
    layers, hlo = getattr(run, "layers", None), getattr(run, "hlo_text", None)
    if layers is None or hlo is None:
        return None
    places = scopes.op_places(hlo)
    if not any(p.scope == "kv_cache.update" for p in places.values()):
        return None
    n = layers.program_calls.get(MODULE, 0)
    sec = scopes.cache_write_s(layers.program_op_s.get(MODULE, {}), places)
    return 1e3 * sec / n if n else None

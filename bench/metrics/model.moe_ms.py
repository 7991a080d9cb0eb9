"""Device time of the expert layers per execution of the serve step, in ms:
the operations in the named scopes ``moe.router`` and ``moe.experts``
(inside ``mlp``), which ``bench/scopes.py`` finds when it is handed them.

It reads the step's optimized HLO text (``run.hlo_text``) and the device
time by program (``run.layers``); a program without the scopes reads None."""

from bench import scopes

MODULE = "jit_serve_step"
MOE = ("moe.router", "moe.experts")


def read(run):
    layers, hlo = getattr(run, "layers", None), getattr(run, "hlo_text", None)
    if layers is None or hlo is None:
        return None
    places = scopes.op_places(hlo, scopes.SCOPES + MOE)
    if not any(p.scope in MOE for p in places.values()):
        return None
    n = layers.program_calls.get(MODULE, 0)
    by = scopes.by_scope(layers.program_op_s.get(MODULE, {}), places)
    return 1e3 * sum(by.get(s, 0.0) for s in MOE) / n if n else None

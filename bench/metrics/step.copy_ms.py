"""Device time of the copies XLA put into the serve step, per execution,
in ms: its operations with no ``op_name`` whose opcode is ``copy``,
``copy-start`` or ``copy-done`` (``bench/scopes.py``), such as the copy of
the donated cache.

It reads the step's optimized HLO text (``run.hlo_text``) and the device
time by program (``run.layers``); a run without them reads None."""

from bench import scopes

MODULE = "jit_serve_step"


def read(run):
    layers, hlo = getattr(run, "layers", None), getattr(run, "hlo_text", None)
    if layers is None or hlo is None:
        return None
    n = layers.program_calls.get(MODULE, 0)
    sec = scopes.copy_s(layers.program_op_s.get(MODULE, {}), scopes.op_places(hlo))
    return 1e3 * sec / n if n else None

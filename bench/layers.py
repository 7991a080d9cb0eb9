"""A traced window split by layer, and short traces of a cell's step for the tests.

    python bench/layers.py --workload <cell> --seed <n> --record <dir>

``split`` logs what a traced ``bench/run.py`` run read from the engine's
``serve.*`` spans (``bench/spans.py``) and the serve step's optimized HLO
text (``bench/scopes.py``): the host's time by span, the idle time by the
span that held it, and the serve step's device time by named scope.

``--record`` writes a short trace of the cell's step for the tests: a few
decode steps and one admission, with the step's HLO text.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Any, Callable, Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path.insert(0, str(ROOT))

from bench import scopes, spans  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

MODULE = "jit_serve_step"


def split(layers: spans.Layers, hlo_text: Optional[str], log: Callable = print) -> Dict[str, Any]:
    """Log the window by span and the serve step by scope; returns both."""
    out: Dict[str, Any] = {"span_ms": {}, "idle_s": {}, "scope_ms": {}}
    for name, (n, s) in sorted(layers.span_s.items()):
        out["span_ms"][name] = [n, 1e3 * s / n]
    idle = sum(layers.idle_by_span.values())
    out["idle_s"] = dict(sorted(layers.idle_by_span.items(), key=lambda x: -x[1]))
    log(f"host spans in the window (count, mean ms): {out['span_ms']}")
    log(f"idle {idle:.6f} s of {layers.window_s:.3f} s, by the span that held it: "
        + ", ".join(f"{k} {v:.6f} s ({100 * v / idle:.2f}%)" for k, v in out["idle_s"].items()))
    log(f"longest idle gaps by innermost span: {layers.gaps[:10]}")
    n = layers.program_calls.get(MODULE, 0)
    op_s = layers.program_op_s.get(MODULE, {})
    log(f"programs (executions in the window): {layers.program_calls}")
    if hlo_text is None or not n:
        return out
    places = scopes.op_places(hlo_text)
    by = scopes.by_scope(op_s, places)
    out["scope_ms"] = {k: 1e3 * v / n for k, v in sorted(by.items(), key=lambda x: -x[1])}
    total = sum(op_s.values())
    out["covered"] = 1 - by.get("unknown", 0.0) / total if total else None
    log(f"{MODULE} device ms per execution by scope ({n} executions, "
        f"{1e3 * total / n:.4f} ms): " + ", ".join(f"{k} {v:.4f}" for k, v in out["scope_ms"].items()))
    top = sorted(op_s.items(), key=lambda x: -x[1])[:12]
    log("its operations with the most device time (ms per execution, scope, primitive): "
        + "; ".join(f"{op} {1e3 * s / n:.4f} {places.get(op, ('unknown', '?'))[0]} "
                    f"{places.get(op, ('unknown', '?'))[1]}" for op, s in top))
    return out


def record(cell, seed: int, out_dir: str, log: Callable = print, *,
           require_tpu: bool = True, cfg=None) -> None:
    """Trace three ``step()`` calls of the cell's engine at its full size: one
    that finishes a request, one that admits the next (one prefill call), one
    more decode. Writes the trace and the step's HLO text to ``out_dir``."""
    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model
    from repro.serve import Request, ServingEngine

    from bench import run as bench_run
    from bench import weights

    if require_tpu:
        bench_run.check_device(cell.chips)
        use_compile_cache()
    conf = cell.config
    cfg = cfg if cfg is not None else bench_run.model_config(conf)
    model = build_model(cfg)
    params = weights.make(model.shapes(), seed, 0.02)
    engine = ServingEngine(model, params, n_slots=int(conf["n_slots"]), max_len=int(conf["max_len"]),
                           on_token=lambda req, tok: False, on_finish=lambda req: None)
    hlo = engine.compile().as_text()
    rng = np.random.default_rng(seed)
    n = int(conf["n_slots"])
    for rid in range(n + 1):
        engine.submit(Request(request_id=rid, max_new_tokens=2 if rid == 0 else 64,
                              prompt=rng.integers(0, cfg.vocab_size, 4 if rid < n else 2, dtype=np.int32)))
    engine.step()                                         # admits n, compiles the small programs
    tdir = tempfile.mkdtemp(prefix="bench_record_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine.step"):
                engine.step()
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out_dir, "steps.xplane.pb"))
    with gzip.open(os.path.join(out_dir, "step.hlo.gz"), "wt") as f:
        f.write(hlo)
    shutil.rmtree(tdir, ignore_errors=True)
    log(f"recorded {out_dir}: stats {engine.stats}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", required=True,
                    help="record a short trace of the cell's step into this directory")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as bench_run

    record(bench_run.find_cell(args.workload), args.seed, args.record,
           log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())

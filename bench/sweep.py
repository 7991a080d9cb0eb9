"""Find the knee of an open-loop mix on one configuration, by a sweep of offered rates on the chip.

    python bench/sweep.py --config phi4-mini-3.8b --traffic chat-burst --seed <n> \
        --rates 0.3,0.4,0.5,0.6,0.7 --schedules 1202,1203 --seconds 60

The configuration is one of ``BENCHMARK.json``'s and the mix a file under
``bench/traffic/``, so a knee can be found before a cell offers the mix.
One process sets up the model and weights once. For each rate, from
the lowest, and each schedule (a ``trace_seed`` of the mix, which fixes the
sizes and arrival times), it builds a fresh ``ServingEngine`` (its step
loads from the compile cache), runs the mix's pre-roll and a window of
``--seconds`` at that rate, and prints one JSON line: the requests offered
and completed per second, those waiting or in a slot when the window
closed, and ``ttft_p95_s`` and ``tokens_per_s`` as their
readers compute them, with ``ttft_p95_s`` also read over each half of the
window alone, to show a backlog that grows. A window is sustained where it completed at least
90% of what it offered and no more requests were outstanding at its close
than the engine has slots. The knee is the last rate before the first
that some schedule does not sustain; the sweep stops at that rate. Record
the knee in the mix file's ``knee_req_per_s``; a cell offers
``rate_share_of_knee`` of it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, traffic, weights  # noqa: E402


def window(config, model, params, mix, seed, seconds, model_sizes):
    """One window of ``mix`` on a fresh engine; returns its row."""
    import jax

    from repro.serve import Request, ServingEngine

    n_slots, max_len = int(config["n_slots"]), int(config["max_len"])
    tr = traffic.build(mix, seed, seconds, model_sizes["vocab_size"], max_len)
    client = run.Client(tr, Request, jax.profiler.TraceAnnotation)
    engine = ServingEngine(model, params, n_slots=n_slots, max_len=max_len,
                           on_token=client.on_token, on_finish=client.on_finish)
    engine.compile()
    t_open = client.preroll(engine)
    stats_open = replace(engine.stats)
    t_close = t_open + seconds
    client.run_until(t_close)
    rec = client.rec
    r = run.Run(model=model_sizes, reference=run.reference_module(config["reference"], ROOT),
                seconds=seconds, t_open=t_open, t_close=t_close,
                setup_s=0.0, rec=rec, stats_open=stats_open, stats_close=replace(engine.stats),
                peak_bytes=None, peaks=None)
    offered = sum(1 for q in tr.schedule if tr.preroll_s <= q.due < tr.preroll_s + seconds)
    done = sum(1 for f in rec.finished.values() if t_open <= f < t_close)
    backlog = client.outstanding() + sum(
        1 for q in tr.schedule[client._next:] if client.t_sched + q.due < t_close)
    ttft = run.reader("ttft_p95_s")
    t_mid = t_open + seconds / 2
    row = {"rate": traffic.rate(mix), "schedule": mix["trace_seed"],
           "offered_req_per_s": offered / seconds, "completed_req_per_s": done / seconds,
           "outstanding_at_close": backlog,
           "ttft_p95_s": ttft(r), "tokens_per_s": run.reader("tokens_per_s")(r),
           "ttft_p95_s_by_half": [ttft(replace(r, t_close=t_mid)), ttft(replace(r, t_open=t_mid))],
           "sustained": done >= 0.9 * offered and backlog <= n_slots}
    client.engine = None
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a configuration of BENCHMARK.json")
    ap.add_argument("--traffic", required=True, help="an open-loop mix under bench/traffic/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--schedules", default=None,
                    help="comma-separated trace seeds of the mix (default: the mix's own)")
    ap.add_argument("--seconds", type=float, default=60.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))

    import jax

    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model

    configs = {c["name"]: c for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]}
    config = json.loads((ROOT / configs[args.config]["file"]).read_text())
    base = json.loads((ROOT / "bench" / "traffic" / f"{args.traffic}.json").read_text())
    run.check_device(1)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = run.model_config(config)
    model = build_model(cfg)
    params = weights.make(model.shapes(), args.seed)
    schedules = ([int(s) for s in args.schedules.split(",")] if args.schedules
                 else [int(base["trace_seed"])])
    knee = None
    for rate in sorted(float(x) for x in args.rates.split(",")):
        ok = True
        for sched in schedules:
            mix = dict(base, trace_seed=sched, arrivals=dict(base["arrivals"], rate_req_per_s=rate))
            t = time.monotonic()
            row = window(config, model, params, mix, args.seed, args.seconds,
                         run.model_dict(cfg, config))
            row["wall_s"] = time.monotonic() - t
            print(json.dumps(row), flush=True)
            ok = ok and row["sustained"]
            gc.collect()
        if not ok:
            break
        knee = rate
    print(json.dumps({"knee_req_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. One process holds the chip and does
everything. Set-up builds the model through ``build_model``, makes random
weights on the device from the seed, builds a ``ServingEngine`` with the
benchmark's ``on_token``/``on_finish`` hooks, compiles its step, and runs
the traffic's pre-roll. The window then drives ``ServingEngine.submit`` and
``ServingEngine.step`` alone for ``--seconds``. After it, the run compares a
sample of the requests it finished with the float32 reference
(``bench/reference/``) and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced) and last ``checks``, each number
compared beside its limit.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json``, the configuration's model
module (its ``"reference"``) in ``bench/reference/<module>.py``, its traffic
in ``bench/traffic/<traffic>.json``, its limits in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer ones. A traced run hands the readers
the window's profiler trace, the engine's spans in it (``bench/spans.py``)
and the serve step's optimized HLO text, and logs the window by span and
the step by named scope (``bench/layers.py``).

The run exits non-zero and prints no result line where JAX's first device
is not a TPU, where it has fewer chips than the cell asks for, or where the
checkout holds no program (``src/repro``).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from collections import defaultdict  # noqa: E402
from dataclasses import dataclass, field, replace  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Import the benchmark as the package ``bench`` from the checkout's root, so
# that its module ``bench/trace.py`` never stands in for the standard one.
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path.insert(0, str(ROOT))

from bench import traffic as traffic_mod  # noqa: E402

SAMPLE_TOKENS = 384          # served tokens compared with the reference, at least
COUNTERS = ("steps", "admissions", "prefill_calls", "prefill_tokens")   # logged over the window


class NoChip(RuntimeError):
    pass


# --------------------------------------------------------------------------- lookup
@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    mix: dict                # bench/traffic/<traffic>.json
    limits: dict             # bench/limits/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    file = configs[w["config"]]["file"]
    config = _json(root / file)
    if "reference" not in config:
        raise KeyError(f"{file} names no model module: give it a \"reference\" key, the name "
                       f"of a module under bench/reference/")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)],
        root=root,
    )


def reader(metric: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def reference_module(name: str, root: Path):
    """The model module ``bench/reference/<name>.py`` that a configuration
    names: its ``served_gaps``, ``token_flops`` and ``attended`` (see
    ``bench/reference/__init__.py``). Loaded once per process, so that its
    jitted functions compile once."""
    path = root / "bench" / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------- the client
@dataclass
class StepCall:
    t0: float
    t1: float
    prefill: List[int]       # kernel lengths of the single-token prefill calls
    decode: List[int]        # kernel lengths of the active slots in the decode call


@dataclass
class Record:
    """What the benchmark saw of the window, from its own hooks and clock."""
    reqs: Dict[int, Any] = field(default_factory=dict)           # rid -> traffic.Req
    due: Dict[int, float] = field(default_factory=dict)
    submitted: Dict[int, float] = field(default_factory=dict)
    tokens: Dict[int, List[float]] = field(default_factory=lambda: defaultdict(list))
    served: Dict[int, List[int]] = field(default_factory=lambda: defaultdict(list))
    finished: Dict[int, float] = field(default_factory=dict)
    steps: List[StepCall] = field(default_factory=list)


class Client:
    """Offers the traffic to the engine and times every token in ``on_token``.

    Open loop: a request is submitted once its due time has passed, before
    the next ``step``. Closed loop: a client sends its next request as soon
    as its previous one finishes. ``on_token`` never stops a generation."""

    def __init__(self, traffic, request_cls, annotate: Callable):
        self.traffic, self.Request, self.annotate = traffic, request_cls, annotate
        self.rec = Record()
        self.engine = None
        self.t_sched = 0.0
        self._next = 0                                   # open loop: schedule index
        self._queue: List = []                           # closed loop: requests ready to send
        self._cursor = [0] * len(traffic.clients)
        self._cur: List[int] = []

    # hooks ------------------------------------------------------------------
    def on_token(self, req, tok: int) -> bool:
        self.rec.tokens[req.request_id].append(time.monotonic())
        self.rec.served[req.request_id].append(int(tok))
        self._cur.append(req.request_id)
        return False

    def on_finish(self, req) -> None:
        now = time.monotonic()
        self.rec.finished[req.request_id] = now
        c = self.rec.reqs[req.request_id].client
        if self.traffic.loop == "closed":
            self._release(c, now)

    # load -------------------------------------------------------------------
    def _release(self, c: int, now: float) -> None:
        seq = self.traffic.clients[c]
        r = seq[self._cursor[c] % len(seq)]
        self._cursor[c] += 1
        self._queue.append((now, r))

    def next_due(self) -> Optional[float]:
        if self.traffic.loop == "closed":
            return min((d for d, _ in self._queue), default=None)
        s = self.traffic.schedule
        return self.t_sched + s[self._next].due if self._next < len(s) else None

    def pump(self, now: float) -> None:
        due = []
        if self.traffic.loop == "closed":
            due = [(d, r) for d, r in self._queue if d <= now]
            self._queue = [(d, r) for d, r in self._queue if d > now]
        else:
            s = self.traffic.schedule
            while self._next < len(s) and self.t_sched + s[self._next].due <= now:
                due.append((self.t_sched + s[self._next].due, s[self._next]))
                self._next += 1
        if not due:
            return
        with self.annotate("client.submit"):
            for d, r in due:
                rid = len(self.rec.reqs)
                self.rec.reqs[rid] = r
                self.rec.due[rid] = d
                self.rec.submitted[rid] = time.monotonic()
                self.engine.submit(self.Request(request_id=rid, prompt=r.prompt,
                                                max_new_tokens=r.max_new, eos_token=None))

    def preroll(self, engine) -> Optional[float]:
        """Start the traffic and run its pre-roll, which also warms up every
        small program the engine's step dispatches. Closed loop: every
        client's first requests, until each has a token. Open loop: the
        schedule's first ``preroll_s``; returns when the window opens on the
        schedule's clock, even while the pre-roll's last step still runs."""
        now = time.monotonic()
        self.engine, self.t_sched = engine, now
        if self.traffic.loop == "closed":
            for _ in range(self.traffic.admit_each):
                for c in range(len(self.traffic.clients)):
                    self._release(c, now)
            first = len(self.traffic.clients) * self.traffic.admit_each
            self.run_until(now + 3600, until=lambda: len(self.rec.tokens) >= first)
            return None
        self.run_until(now + self.traffic.preroll_s)
        return now + self.traffic.preroll_s

    def outstanding(self) -> int:
        return len(self.rec.submitted) - len(self.rec.finished)

    def step(self) -> None:
        self._cur = []
        t0 = time.monotonic()
        with self.annotate("engine.step"):
            self.engine.step()
        t1 = time.monotonic()
        prefill, decode = [], []
        for rid in self._cur:
            g, p = len(self.rec.tokens[rid]), len(self.rec.reqs[rid].prompt)
            if g == 1:
                prefill.extend(range(1, p))
            decode.append(p + g - 1)
        self.rec.steps.append(StepCall(t0, t1, prefill, decode))

    def run_until(self, t_end: float, until: Optional[Callable[[], bool]] = None) -> None:
        while True:
            now = time.monotonic()
            if now >= t_end or (until is not None and until()):
                return
            self.pump(now)
            if self.outstanding():
                self.step()
                continue
            nxt = self.next_due()
            wait = (t_end if nxt is None else min(nxt, t_end)) - now
            if wait > 0:
                with self.annotate("bench.wait"):
                    time.sleep(wait)


# --------------------------------------------------------------------------- a run
@dataclass
class Run:
    """What a metric reader gets."""
    model: dict              # the configuration file's ``model`` keys, as the program runs them
    reference: Any           # the configuration's model module (``reference_module``)
    seconds: float
    t_open: float
    t_close: float
    setup_s: float
    rec: Record
    stats_open: Any
    stats_close: Any
    peak_bytes: Optional[int]
    peaks: Optional[dict]
    trace: Any = None        # bench.trace.Reduced when traced
    layers: Any = None       # bench.spans.Layers when traced
    hlo_text: Optional[str] = None   # the serve step's optimized HLO text when traced

    def window_steps(self) -> List[StepCall]:
        return [s for s in self.rec.steps if self.t_open <= s.t0 < self.t_close]


def model_config(config: dict):
    """The registry configuration, checked against the configuration file."""
    from repro.configs import get_config

    cfg = get_config(config["arch"]).with_(dtype=config["dtype"])
    for k, v in config["model"].items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{config['arch']}: the file says {k}={v!r}, the program runs "
                             f"{getattr(cfg, k)!r}")
    return cfg


def model_dict(cfg, config: dict) -> dict:
    """Every key of the configuration file's ``model``, as ``cfg`` holds it."""
    return {k: getattr(cfg, k) for k in config["model"]}


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices


def compare(model: dict, ref, params, client: Client, seed: int,
            control: bool = False) -> Dict[str, Any]:
    """Widest logit gap of a seeded sample of finished requests, longest
    included, by the model module ``ref``'s ``served_gaps``.

    With ``control`` the gaps are the float8 control's, read at the same
    positions in place of the served tokens', and ``program_gaps`` keeps the
    served tokens' for the log."""
    rec = client.rec
    done = sorted(rec.finished, key=lambda r: (-len(rec.served[r]), r))
    rng = np.random.default_rng(seed)
    sample = done[:1] + [done[1:][i] for i in rng.permutation(len(done) - 1)] if done else []
    picked, n_tok = [], 0
    for rid in sample:
        if n_tok >= SAMPLE_TOKENS:
            break
        picked.append(rid)
        n_tok += len(rec.served[rid])
    gaps, program_gaps = [], []
    for rid in picked:
        out = ref.served_gaps(model, params, rec.reqs[rid].prompt,
                              np.asarray(rec.served[rid], np.int32), control=control)
        program_gaps.append(float(out["served"].max()))
        gaps.append(float(out["control" if control else "served"].max()))
    served_all = [t for rid in rec.finished for t in rec.served[rid]]
    return {"requests": len(picked), "tokens": n_tok, "gaps": gaps, "program_gaps": program_gaps,
            "max_logit_gap": max(gaps, default=float("inf")),
            "out_of_vocab": sum(not 0 <= t < model["vocab_size"] for t in served_all)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cfg=None, std: float = 0.02, control: bool = False,
             log=print) -> dict:
    """Set up, run the window, check the answers; returns the result object.

    ``control`` puts the float8 control in the program's place for the
    comparison: its gaps go through the same checks, so the run reads not
    correct."""
    import jax

    from repro.serve import Request, ServingEngine

    from bench import weights

    devices = check_device(cell.chips) if require_tpu else jax.devices()
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    lowered = []

    def on_compile_event(ev, _dur, **_kw):
        if ev.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(ev)

    jax.monitoring.register_event_duration_secs_listener(on_compile_event)

    from repro.models import build_model

    conf = cell.config
    cfg = cfg if cfg is not None else model_config(conf)
    ref = reference_module(conf["reference"], cell.root)
    n_slots, max_len = int(conf["n_slots"]), int(conf["max_len"])
    model = build_model(cfg)
    t = time.monotonic()
    params = weights.make(model.shapes(), seed, std)
    jax.block_until_ready(params)
    log(f"set-up: weights {time.monotonic() - t:.3f} s")
    tr = traffic_mod.build(cell.mix, seed, seconds, cfg.vocab_size, max_len)
    annotate = jax.profiler.TraceAnnotation
    client = Client(tr, Request, annotate)
    engine = ServingEngine(model, params, n_slots=n_slots, max_len=max_len,
                           on_token=client.on_token, on_finish=client.on_finish)
    t = time.monotonic()
    compiled = engine.compile()
    log(f"set-up: step compiled or loaded in {time.monotonic() - t:.3f} s")

    # Collect what set-up left behind (tracing, compiling, the weights' tree)
    # now, so that a full collection of it does not fall at a random point of
    # the window. Full collections that the window's own work brings about
    # still happen there and are logged.
    gc.collect()
    full_gc: List[float] = []

    def on_gc(phase, info):
        if info["generation"] == 2:
            full_gc.append(time.monotonic() if phase == "start" else -time.monotonic())

    gc.callbacks.append(on_gc)
    t_open = client.preroll(engine)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    n_lowered = len(lowered)
    stats_open = replace(engine.stats)
    t_open = t_open or time.monotonic()
    setup_s = t_open - T_START
    t_close = t_open + seconds
    with annotate("bench.window"):
        client.run_until(t_close)
    stats_close = replace(engine.stats)
    lowered_in_window = len(lowered) - n_lowered
    jax.monitoring.unregister_event_duration_listener(on_compile_event)
    gc.callbacks.remove(on_gc)
    gc_in_window = [(a, -b) for a, b in zip(full_gc[0::2], full_gc[1::2]) if a >= t_open]
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    reduced = layers = hlo_text = None
    if trace:
        jax.profiler.stop_trace()
        from bench import spans
        from bench import trace as trace_mod

        t = time.monotonic()
        planes, host = trace_mod.events(tdir, spans.wanted)
        window = trace_mod.clip(planes, host)
        del planes
        # The idle gaps are named once, by the engine's spans.
        reduced = trace_mod.reduce_window(window)
        layers = spans.reduce_window(window, host)
        del window, host
        log(f"trace read in {time.monotonic() - t:.3f} s")
        shutil.rmtree(tdir, ignore_errors=True)
        hlo_text = compiled.as_text()

    dev = devices[0]
    peaks = None
    if require_tpu:
        from bench import peaks as peaks_mod

        peaks = peaks_mod.peaks(dev.device_kind)
    run = Run(model=model_dict(cfg, conf), reference=ref, seconds=seconds,
              t_open=t_open, t_close=t_close, setup_s=setup_s, rec=client.rec,
              stats_open=stats_open, stats_close=stats_close, peak_bytes=peak,
              peaks=peaks, trace=reduced, layers=layers, hlo_text=hlo_text)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"], cell.root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # Counts of the window, for the log.
    rec = client.rec
    due = [r for r, d in rec.due.items() if t_open <= d < t_close]
    win = run.window_steps()
    lag = [rec.submitted[r] - rec.due[r] for r in due]
    log(f"window: {seconds} s; requests due {len(due)}, "
        f"admitted {sum(1 for r in due if rec.tokens.get(r))}, "
        f"finished {sum(1 for f in rec.finished.values() if t_open <= f < t_close)}; "
        f"step() calls {len(win)}, serve-step calls that the hooks imply: prefill "
        f"{sum(len(s.prefill) for s in win)}, decode {len(win)}; programs lowered in the window {lowered_in_window}; "
        f"full garbage collections in the window {len(gc_in_window)}, "
        f"{sum(b - a for a, b in gc_in_window):.3f} s")
    stalls = sorted((s.t1 - s.t0 for s in win if not s.prefill), reverse=True)[:3]
    log(f"longest step() calls without an admission: {[round(x, 4) for x in stalls]} s")
    log(f"submission lag (due until submitted, counted in TTFT; includes waiting for the "
        f"engine's step() to return): max {max(lag, default=0.0):.6f} s, "
        f"mean {np.mean(lag) if lag else 0.0:.6f} s")
    log(f"peak_bytes_in_use {peak}; set-up {setup_s:.4f} s")
    counters = {k: getattr(stats_close, k) - getattr(stats_open, k) for k in COUNTERS}
    log(f"engine counters over the window: {json.dumps(counters)}")
    if layers is not None:
        from bench import layers as layers_mod

        layers_mod.split(layers, hlo_text, log)

    # Free the engine's cache before the reference runs; the weights stay.
    engine = client.engine = None
    gc.collect()
    t = time.monotonic()
    checks_raw = compare(run.model, ref, params, client, seed, control=control)
    log(f"reference: {checks_raw['requests']} requests, {checks_raw['tokens']} tokens, "
        f"program gaps {checks_raw['program_gaps']} in {time.monotonic() - t:.3f} s")
    if control:
        log(f"control (float8 e4m3) gaps {checks_raw['gaps']}")
    lim = cell.limits
    checks = {
        "max_logit_gap": {"value": checks_raw["max_logit_gap"], "max": lim["max_logit_gap"]},
        "tokens_compared": {"value": checks_raw["tokens"], "min": lim["min_tokens_compared"]},
        "nonfinite_steps": {"value": stats_close.nonfinite_steps, "max": 0},
        "out_of_vocab_tokens": {"value": checks_raw["out_of_vocab"], "max": 0},
    }
    correct = all(c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"]
                  for c in checks.values())
    failed = sum(g > lim["max_logit_gap"] for g in checks_raw["gaps"])
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": [[n, s] for n, s in layers.gaps[:10]]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the float8 control in the program's place (reads not correct); "
                         "a measured run leaves it off")
    args = ap.parse_args(argv)
    # The TPU runtime logs under /tmp unless told otherwise; keep its logs
    # in this run's own temporary directory.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: {ROOT} holds no program (src/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = find_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=bool(args.control), log=lambda m: print(m, flush=True))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    except Exception:  # noqa: BLE001 - any failure ends the run with no result
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        bound = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name}: {c['value']} (limit {bound})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

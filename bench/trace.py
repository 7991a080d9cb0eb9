"""Reduce a profiler trace of the measured window to the benchmark's numbers.

The run records the window with ``jax.profiler`` and marks it with host
spans (``jax.profiler.TraceAnnotation``) from the benchmark's own code:
``bench.window`` around the traced window, and inside it ``client.submit``,
``engine.step`` and ``bench.wait`` around the calls it makes. The device
planes (``/device:TPU:<n>``) hold the line ``XLA Ops``, one event per
operation, nested (a ``while`` holds its body), and ``XLA Modules``, one
event per execution of a compiled program. Host and device events share
one clock in the trace.

From these it computes, within the window:

- busy time: the union of the operation intervals of each device,
  averaged over the devices;
- each operation's self time (its duration less that of the operations
  nested in it) and its executions, by the name the compiled program
  gives it;
- each program's executions and device time;
- the idle gaps between busy intervals, each named by the host span that
  overlaps it most (``host.other`` where none does).

``clip`` cuts the trace to the window once; ``reduce_window`` and
``bench/spans.py``'s ``reduce_window`` both take what it returns. A run
names its gaps by the engine's spans there, and asks this module for none.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

WINDOW = "bench.window"
SPANS = ("client.submit", "engine.step", "bench.wait")

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_self_s: Dict[str, float]
    modules: Dict[str, Tuple[int, float]]  # program -> (executions, seconds)
    gaps: List[Tuple[str, float]] = field(default_factory=list)   # longest first
    n_devices: int = 1
    op_calls: Dict[str, int] = field(default_factory=dict)

    def kernel_s(self, kernel: str) -> float:
        """Device time of a Pallas kernel: its calls are named ``<kernel>.<n>``."""
        return sum(s for op, s in self.op_self_s.items()
                   if op == kernel or op.startswith(kernel + "."))

    def kernel_calls(self, kernel: str) -> int:
        """Executions of a Pallas kernel, one per call in every layer."""
        return sum(n for op, n in self.op_calls.items()
                   if op == kernel or op.startswith(kernel + "."))

    def module(self, prefix: str) -> Tuple[int, float]:
        n = sec = 0
        for name, (k, s) in self.modules.items():
            if name.startswith(prefix):
                n, sec = n + k, sec + s
        return n, sec

    def top_ops(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in sorted(self.op_self_s.items(), key=lambda x: -x[1])[:k]]


def op_name(event_name: str) -> str:
    """``%fusion.103 = bf16[...] fusion(...)`` -> ``fusion.103``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_serve_step(1675...)`` -> ``jit_serve_step``."""
    return event_name.split("(", 1)[0]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Seconds of each operation by name, less what is nested inside it."""
    acc: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float]] = []            # (name, end_ns)
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            acc[stack[-1][0]] -= min(dur, stack[-1][1] - start) / 1e9
        acc[name] += dur / 1e9
        stack.append((name, start + dur))
    return dict(acc)


@dataclass
class Window:
    """The trace cut to the window, as both reductions take it
    (``spans.reduce_window`` too)."""
    t0: float
    t1: float
    ops: List[List[Event]]                  # per device: ops by name, cut to the window, in start order
    modules: List[Sequence[Event]]          # per device: program executions, as read
    busy: List[List[Tuple[float, float]]]   # per device: the union of its ops' intervals

    @property
    def gaps(self) -> List[Tuple[float, float]]:
        """The first device's idle intervals in the window, in ns."""
        busy = self.busy[0] if self.busy else []
        edges = [self.t0] + [x for a, b in busy for x in (a, b)] + [self.t1]
        return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def clip(devices: Sequence[Tuple[Sequence[Event], Sequence[Event]]],
         host: Sequence[Event]) -> Window:
    """``devices``: per device, its (ops, modules) events; ``host``: span events."""
    windows = [e for e in host if e[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    _, t0, dur = windows[0]
    t1 = t0 + dur
    ops_in, busy = [], []
    for ops, _ in devices:
        cut = sorted(((op_name(n), max(s, t0), min(s + d, t1) - max(s, t0))
                      for n, s, d in ops if s < t1 and s + d > t0), key=lambda e: (e[1], -e[2]))
        ops_in.append(cut)
        busy.append(union((s, s + d) for _, s, d in cut))
    return Window(t0=t0, t1=t1, ops=ops_in, modules=[m for _, m in devices], busy=busy)


def reduce_window(w: Window, host: Sequence[Event] = ()) -> Reduced:
    """The benchmark's device numbers; the idle gaps named by the
    benchmark's spans in ``host``, or none where ``host`` is empty."""
    busy_ns, ops_all, mods = 0.0, defaultdict(float), defaultdict(lambda: [0, 0.0])
    calls: Dict[str, int] = defaultdict(int)
    for ops_in, modules, busy in zip(w.ops, w.modules, w.busy):
        busy_ns += sum(b - a for a, b in busy)
        for name, sec in self_times(ops_in).items():
            ops_all[name] += sec
        for name, _, _ in ops_in:
            calls[name] += 1
        for n, s, d in modules:
            if w.t0 <= s < w.t1:
                m = mods[module_name(n)]
                m[0] += 1
                m[1] += d / 1e9
    n_dev = max(len(w.ops), 1)
    spans = [e for e in host if e[0] in SPANS]
    gaps = []
    for a, b in (w.gaps if host else []):
        best, over = "host.other", 0.0
        for name, s, d in spans:
            o = min(b, s + d) - max(a, s)
            if o > over:
                best, over = name, o
        gaps.append((best, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w.t1 - w.t0) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                   op_self_s=dict(ops_all),
                   modules={k: (v[0], v[1]) for k, v in mods.items()}, gaps=gaps,
                   n_devices=len(w.ops), op_calls=dict(calls))


def reduce_events(devices: Sequence[Tuple[Sequence[Event], Sequence[Event]]],
                  host: Sequence[Event]) -> Reduced:
    """``devices``: per device, its (ops, modules) events; ``host``: span events."""
    return reduce_window(clip(devices, host), host)


def events(log_dir: str, keep: Callable[[str], bool]
           ) -> Tuple[List[Tuple[List[Event], List[Event]]], List[Event]]:
    """Read the one ``.xplane.pb`` under ``log_dir``: per TPU, its (ops,
    modules) events, and the host's span events whose name ``keep`` takes."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            get = lambda ln: [(e.name, e.start_ns, e.duration_ns)   # noqa: E731
                              for e in lines[ln].events] if ln in lines else []
            devices.append((get("XLA Ops"), get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if keep(e.name))
    return devices, host


def load(log_dir: str) -> Reduced:
    """Read the one ``.xplane.pb`` under ``log_dir`` and reduce it."""
    wanted = set(SPANS) | {WINDOW}
    return reduce_events(*events(log_dir, wanted.__contains__))

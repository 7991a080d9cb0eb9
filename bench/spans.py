"""What the host and each program did in a traced window, layer by layer.

``bench/trace.py`` reduces the window to the benchmark's device numbers and
names each idle gap by the benchmark's own span that overlaps it most.
This module reads the same ``.xplane.pb`` for what the engine marks itself
(``serve.*`` spans, see ``src/repro/serve/engine.py``):

- each span's count and host seconds in the window;
- each idle gap named by what the host was doing in it, most of the time:
  the gap is cut at the edges of the spans, each piece goes to the
  innermost span that covers it (``host.other`` where none does), and the
  gap takes the name of the span that holds the most of it. The
  benchmark's own spans do not nest, so without engine spans every gap
  reads as ``trace.Reduced.gaps`` names it;
- the window's idle seconds by the span that held each piece;
- each device operation given to the program execution whose interval
  holds it, and its self time by program.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

from bench import trace

ENGINE = "serve."                           # prefix of the engine's own spans
OTHER = "host.other"
NO_PROGRAM = "none"

Event = trace.Event                         # (name, start_ns, duration_ns)
Segment = Tuple[float, float, int]          # (start_ns, end_ns, index of the innermost span)


@dataclass
class Layers:
    window_s: float
    span_s: Dict[str, Tuple[int, float]]           # span -> (count, host seconds), in the window
    gaps: List[Tuple[str, float]]                  # idle gaps by innermost span, longest first
    idle_by_span: Dict[str, float]                 # idle seconds by the span that held each piece
    program_calls: Dict[str, int]                  # program -> executions that start in the window
    program_op_s: Dict[str, Dict[str, float]]      # program -> operation -> device self seconds


def wanted(name: str) -> bool:
    return name == trace.WINDOW or name in trace.SPANS or name.startswith(ENGINE)


def innermost(spans: Sequence[Event]) -> List[Segment]:
    """Cut time at the edges of ``spans``; each piece names the span that
    started last among those that cover it (the innermost, where they nest)."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    segs: List[Segment] = []
    stack: List[Tuple[float, int]] = []            # (end_ns, span index)
    t = float("-inf")

    def run_to(x: float) -> None:
        nonlocal t
        while stack:
            end, i = stack[-1]
            if end > x:
                if x > t:
                    segs.append((t, x, i))
                break
            if end > t:
                segs.append((t, end, i))
                t = end
            stack.pop()
        t = max(t, x)

    for i in order:
        _, s, d = spans[i]
        run_to(s)
        stack.append((s + d, i))
    run_to(float("inf"))
    return segs


def name_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[Event]
              ) -> Tuple[List[Tuple[str, float]], Dict[str, float]]:
    """Each gap (sorted, disjoint, in ns) named by the span holding most of
    it, and the idle seconds by the span that held each piece."""
    segs = innermost(spans)
    named, idle = [], defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        held: Dict[int, float] = {}
        k, covered = j, 0.0
        while k < len(segs) and segs[k][0] < b:
            o = min(b, segs[k][1]) - max(a, segs[k][0])
            if o > 0:
                i = segs[k][2]
                held[i] = held.get(i, 0.0) + o
                idle[spans[i][0]] += o / 1e9
                covered += o
            k += 1
        idle[OTHER] += (b - a - covered) / 1e9
        best = max(held, key=lambda i: (held[i], -i)) if held else None
        named.append((spans[best][0] if best is not None else OTHER, (b - a) / 1e9))
    named.sort(key=lambda g: -g[1])
    return named, dict(idle)


def by_program(ops: Sequence[Event], modules: Sequence[Event]) -> Dict[str, Dict[str, float]]:
    """Self time of each operation, by the program execution that holds its start."""
    mods = sorted((s, s + d, trace.module_name(n)) for n, s, d in modules)
    groups: Dict[str, List[Event]] = defaultdict(list)
    j = 0
    for op in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] <= op[1]:
            j += 1
        inside = j < len(mods) and mods[j][0] <= op[1]
        groups[mods[j][2] if inside else NO_PROGRAM].append(op)
    return {p: trace.self_times(evs) for p, evs in groups.items()}


def reduce_window(w: trace.Window, host: Sequence[Event]) -> Layers:
    """``w``: the trace cut to the window (``trace.clip``); ``host``: span
    events. Gaps are those of the first device, as ``trace.reduce_window``
    takes them."""
    t0, t1 = w.t0, w.t1
    spans = [e for e in host if e[0] != trace.WINDOW and e[1] < t1 and e[1] + e[2] > t0]

    span_s: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, s, d in spans:
        if t0 <= s < t1:
            span_s[name][0] += 1
            span_s[name][1] += d / 1e9

    calls: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for ops_in, modules in zip(w.ops, w.modules):
        for n, s, _ in modules:
            if t0 <= s < t1:
                calls[trace.module_name(n)] += 1
        for prog, sec in by_program(ops_in, modules).items():
            for op, x in sec.items():
                op_s[prog][op] += x

    named, idle = name_gaps(w.gaps, spans)
    return Layers(window_s=(t1 - t0) / 1e9,
                  span_s={k: (int(v[0]), v[1]) for k, v in span_s.items()},
                  gaps=named, idle_by_span=idle, program_calls=dict(calls),
                  program_op_s={p: dict(v) for p, v in op_s.items()})


def reduce_events(devices: Sequence[Tuple[Sequence[Event], Sequence[Event]]],
                  host: Sequence[Event]) -> Layers:
    """``devices``: per device, its (ops, modules) events; ``host``: span events."""
    return reduce_window(trace.clip(devices, host), host)


def load(log_dir: str) -> Layers:
    """Read the one ``.xplane.pb`` under ``log_dir`` and reduce it."""
    return reduce_events(*trace.events(log_dir, wanted))

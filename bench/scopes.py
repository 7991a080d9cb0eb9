"""Charge the device time of a compiled step to the named scopes of its source.

The program marks the parts of its decode step with ``jax.named_scope``
(``src/repro/models/scopes.py``; the list is repeated here so that the
benchmark also reads a program that has none). XLA keeps each scope in
the ``op_name`` metadata of the instructions it compiles, and the names
of those instructions are the operation names of a profiler trace. So
the optimized HLO text of the step (``Compiled.as_text()``) maps every
operation of the trace to a part of the model, whatever number a fusion
gets:

- an instruction with an ``op_name`` belongs to the innermost scope of
  the list found in it, or to ``step`` where it holds none of them. A
  reader of a scope that another family's step adds passes the list with
  that scope in it;
- an instruction with no ``op_name`` was put in by XLA itself, such as a
  copy of a donated buffer, and belongs to ``xla.<opcode>``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, NamedTuple, Sequence

SCOPES = ("embed", "layers", "attn", "kv_cache.update", "mlp", "unembed", "sample")
UNSCOPED = "step"
XLA_COPIES = ("xla.copy", "xla.copy-start", "xla.copy-done")

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class Place(NamedTuple):
    scope: str          # innermost scope of the list, UNSCOPED, or xla.<opcode>
    primitive: str      # the last part of the op_name (the JAX primitive), or the opcode


def op_places(hlo_text: str, names: Sequence[str] = SCOPES) -> Dict[str, Place]:
    """Each instruction of the HLO text -> its innermost scope of ``names``
    and its primitive."""
    out: Dict[str, Place] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        meta = _OP_NAME.search(line)
        if meta is None:
            out[name] = Place(f"xla.{opcode}", opcode)
            continue
        parts = meta.group(1).split("/")
        scope = next((p for p in reversed(parts) if p in names), UNSCOPED)
        out[name] = Place(scope, parts[-1])
    return out


def by_scope(op_s: Dict[str, float], places: Dict[str, Place]) -> Dict[str, float]:
    """Seconds of each scope; operations the HLO text lacks go to ``unknown``."""
    acc: Dict[str, float] = defaultdict(float)
    for op, s in op_s.items():
        acc[places[op].scope if op in places else "unknown"] += s
    return dict(acc)


def cache_write_s(op_s: Dict[str, float], places: Dict[str, Place]) -> float:
    """Seconds of the cache write path: everything in ``kv_cache.update``,
    and the scan's ``dynamic_update_slice`` of each layer's cache into its
    stacked output, which XLA charges to ``layers``."""
    return sum(s for op, s in op_s.items() if op in places and (
        places[op].scope == "kv_cache.update"
        or places[op] == Place("layers", "dynamic_update_slice")))


def copy_s(op_s: Dict[str, float], places: Dict[str, Place]) -> float:
    """Seconds of the copies XLA put in (no ``op_name``)."""
    return sum(s for op, s in op_s.items() if op in places and places[op].scope in XLA_COPIES)

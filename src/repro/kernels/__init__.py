"""Pallas TPU kernels for the perf-critical compute of the model substrate.

Each kernel directory contains:
  * ``kernel.py`` — the Pallas TPU kernel (pl.pallas_call + BlockSpec),
    validated on CPU with ``interpret=True``;
  * ``ops.py``    — the public jit'd wrapper with impl dispatch
    (pallas on TPU / XLA or ref elsewhere);
  * ``ref.py``    — the pure-jnp oracle used by tests.
"""

import re

from .flash_attention.ops import flash_attention
from .decode_attention.ops import decode_attention
from .rglru_scan.ops import rglru_scan
from .wkv6.ops import wkv6
from .rmsnorm.ops import rmsnorm

__all__ = ["flash_attention", "decode_attention", "rglru_scan", "wkv6", "rmsnorm",
           "compiled_kernels"]

_TPU_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z_][\w-]*?)(?:\.\d+)?\s*=.*custom_call_target=\"tpu_custom_call\"",
    re.M,
)


def compiled_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels in a compiled TPU program's text
    (``jit(f).lower(...).compile().as_text()``). Each kernel passes its
    name to ``pallas_call``; an empty set means the program took no
    Pallas path."""
    return set(_TPU_CUSTOM_CALL.findall(hlo_text))

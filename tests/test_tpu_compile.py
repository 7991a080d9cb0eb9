"""Compile-only rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with JAX, so the main path's Pallas kernels
are compiled here against a described ``v5e:2x2`` topology at their real
widths (phi4-mini-3.8b: d_model 3072, 24 q / 8 kv heads of 128). The
compiler refuses what interpret mode accepts — blocks that break the
(8, 128) tiling rule, too much VMEM — so these tests catch it for free.
Nothing runs: they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every test
worker imports this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compiled_kernels
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

V5E_HBM_BYTES = 16e9
B, H, KV, D, D_MODEL, MAX_LEN, N_LAYERS = 4, 24, 8, 128, 3072, 2048, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kwargs):
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def test_decode_attention_compiles(one_chip):
    """One layer's cache, and one layer read by index from the stack, at
    phi4-mini's heads; and stacks at 32 q / 4 kv heads (yi-6b, Mellum2),
    full and a ring of 1024, each with the block worked out from its
    shapes."""
    bf = jnp.bfloat16
    q, lengths = _sds((B, H, D), bf, one_chip), _sds((B,), jnp.int32, one_chip)
    layer_cache = _sds((B, KV, MAX_LEN, D), bf, one_chip)
    stacked = _sds((N_LAYERS, B, KV, MAX_LEN, D), bf, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    q32 = _sds((B, 32, D), bf, one_chip)
    kv4 = [_sds((N_LAYERS, B, 4, s, D), bf, one_chip) for s in (MAX_LEN, 1024)]
    for compiled in (
        _compile(decode_attention_pallas, q, layer_cache, layer_cache, lengths),
        _compile(decode_attention_pallas, q, stacked, stacked, lengths, layer),
        *(_compile(decode_attention_pallas, q32, c, c, lengths, layer) for c in kv4),
    ):
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert compiled_kernels(text) == {"decode_attention"}


@pytest.mark.parametrize("rows", [(B, 1), (1, 700)], ids=["decode", "prefill"])
def test_rmsnorm_compiles(one_chip, rows):
    compiled = _compile(
        rmsnorm_pallas,
        _sds(rows + (D_MODEL,), jnp.bfloat16, one_chip),
        _sds((D_MODEL,), jnp.bfloat16, one_chip),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled_kernels(text) == {"rmsnorm"}


def test_flash_attention_forward_compiles(one_chip):
    bf = jnp.bfloat16
    compiled = _compile(
        flash_attention_pallas,
        _sds((1, H, 512, D), bf, one_chip),
        _sds((1, KV, 512, D), bf, one_chip),
        _sds((1, KV, 512, D), bf, one_chip),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled_kernels(text) == {"flash_attention"}


def test_published_serve_step_compiles_with_pallas_kernels(one_chip, monkeypatch):
    """The whole phi4-mini-3.8b serve step at published widths, bf16, as
    ServingEngine compiles it on a TPU: both Pallas kernels present, the
    program within one chip's HBM, and the cache written in place: no
    scratch buffer as large as one layer's cache, and no copy or
    multiply of a cache-sized buffer."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import make_serve_step

    # Off the TPU the ops pick their reference paths; steer them here.
    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    monkeypatch.setenv("REPRO_NORM_IMPL", "pallas")
    model = build_model(get_config("phi4-mini-3.8b"))
    on_chip = lambda tree: jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    compiled = _compile(
        make_serve_step(model),
        on_chip(model.shapes()),
        on_chip(model.cache_shapes(B, MAX_LEN)),
        _sds((B, 1), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip),
        donate_argnums=(1,),                 # as ServingEngine donates the cache
    )
    assert {"decode_attention", "rmsnorm"} <= compiled_kernels(compiled.as_text())
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES
    layer_kv_bytes = 2 * B * KV * MAX_LEN * D * 2
    assert mem.temp_size_in_bytes < layer_kv_bytes
    assert _cache_sized_copies(compiled.as_text()) == []


# An instruction's name, its result's type (a tuple for a multi-output
# fusion) and its opcode.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = (\(.*?\)|\S+) ([\w-]+)\(", re.M)


def _cache_sized_copies(hlo_text):
    """Copies, and multiply fusions, with a result of the shape of the
    stacked cache, of one layer's cache, or of the kernel's view of them."""
    cache_dims = {f"{N_LAYERS},{B},{KV},{MAX_LEN},{D}", f"{B},{KV},{MAX_LEN},{D}",
                  f"{N_LAYERS},{B * KV},{MAX_LEN},{D}", f"{B * KV},{MAX_LEN},{D}",
                  f"1,{B},{KV},{MAX_LEN},{D}"}
    return [name for name, result, opcode in _INSTRUCTION.findall(hlo_text)
            if cache_dims & set(re.findall(r"\[([\d,]*)\]", result)) and (
                opcode in ("copy", "copy-start", "copy-done")
                or (opcode == "fusion" and ("multiply" in name or "copy" in name)))]


def test_window_moe_serve_step_compiles_without_copying_weights(one_chip, monkeypatch):
    """Mellum2's one-chip share at published widths, 8 slots x 2048: the
    step reads its layers out of the stacked weights in place. No expert
    stack, nor a period's slice of it, is copied in the step: only the
    attention projections are laid out anew, as the dense steps do."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import make_serve_step

    monkeypatch.setenv("REPRO_ATTN_IMPL", "pallas")
    monkeypatch.setenv("REPRO_NORM_IMPL", "pallas")
    model = build_model(get_config("mellum2-12b-a2.5b-ep4"))
    on_chip = lambda tree: jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    slots = 8
    compiled = _compile(
        make_serve_step(model),
        on_chip(model.shapes()),
        on_chip(model.cache_shapes(slots, MAX_LEN)),
        _sds((slots, 1), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip),
        donate_argnums=(1,),
    )
    text = compiled.as_text()
    assert {"decode_attention", "rmsnorm"} <= compiled_kernels(text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
    expert_bytes = 16 * 3 * 2304 * 896 * 2          # one layer's held experts
    assert mem.temp_size_in_bytes < 4 * expert_bytes
    copied = [result for result, opcode in _unfused(text)
              if opcode in ("copy", "copy-start", "fusion")
              and re.search(r"\[(\d+,)?16,(2304,896|896,2304)\]", result)]
    assert copied == []


def _unfused(hlo_text):
    """(result type, opcode) of the instructions that make a buffer of their
    own: those outside the computations that fusions call."""
    fused = set(re.findall(r"calls=%([\w.-]+)", hlo_text))
    out, inside = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and inside not in fused:
            out.append((m.group(2), m.group(3)))
    return out

"""Uniform Model facade over the architecture families.

``build_model(cfg)`` returns a ``Model`` exposing:
  * ``defs`` / ``init`` / ``pspecs`` / ``shapes`` — parameter tree
    declaration, materialization, PartitionSpecs, ShapeDtypeStructs;
  * ``forward`` / ``loss`` — full-sequence compute;
  * ``cache_defs`` / ``init_cache`` / ``cache_pspecs`` — decode state;
  * ``decode_step`` — single-token decode;
  * ``input_specs(shape)`` — ShapeDtypeStruct stand-ins for every input
    of the train/prefill/decode step (the dry-run contract).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..configs.base import ModelConfig, ShapeConfig
from . import griffin, rwkv6, transformer, whisper
from .layers import (
    axis_rules,
    init_params,
    param_count,
    param_pspecs,
    param_shapes,
    resolve_pspec,
)

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "rwkv6": rwkv6,
    "griffin": griffin,
    "whisper": whisper,
}


@dataclass
class Model:
    cfg: ModelConfig
    mod: Any

    # ------------------------------------------------------------- params
    @property
    def defs(self):
        return self.mod.model_defs(self.cfg)

    def init(self, rng: jax.Array):
        return init_params(self.defs, rng, self.cfg)

    def pspecs(self, mesh: Mesh):
        return param_pspecs(self.defs, mesh, self.cfg)

    def shapes(self):
        return param_shapes(self.defs, self.cfg)

    def n_params(self) -> int:
        return param_count(self.defs)

    # ------------------------------------------------------------ compute
    def forward(self, params, batch, *, last_only: bool = False):
        return self.mod.forward(self.cfg, params, batch, last_only=last_only)

    def loss(self, params, batch):
        return self.mod.loss_fn(self.cfg, params, batch)

    # ------------------------------------------------------------- decode
    def cache_defs(self, batch: int, max_len: int):
        return self.mod.cache_defs(self.cfg, batch, max_len)

    def init_cache(self, batch: int, max_len: int):
        zeros = jax.random.PRNGKey(0)
        return init_params(self.cache_defs(batch, max_len), zeros, self.cfg)

    def cache_pspecs(self, mesh: Mesh, batch: int, max_len: int):
        return param_pspecs(self.cache_defs(batch, max_len), mesh, self.cfg)

    def cache_shapes(self, batch: int, max_len: int):
        return param_shapes(self.cache_defs(batch, max_len), self.cfg)

    def decode_step(self, params, cache, tokens, lengths, **kw):
        return self.mod.decode_step(self.cfg, params, cache, tokens, lengths, **kw)

    # --------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig, mesh: Optional[Mesh] = None) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for one step's inputs (no allocation).

        train/prefill: {tokens, labels[, frames|patches]};
        decode: {tokens (B,1), lengths (B,)} (cache specs come separately).
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        rules = axis_rules(cfg)

        def spec(shp, dtype, logical):
            if mesh is None:
                return jax.ShapeDtypeStruct(shp, dtype)
            from jax.sharding import NamedSharding
            ps = resolve_pspec(logical, shp, mesh, rules)
            return jax.ShapeDtypeStruct(shp, dtype, sharding=NamedSharding(mesh, ps))

        dt = jnp.dtype(cfg.dtype)
        if shape.kind in ("train", "prefill"):
            out = {
                "tokens": spec((B, S), jnp.int32, ("batch", "seq")),
            }
            if shape.kind == "train":
                out["labels"] = spec((B, S), jnp.int32, ("batch", "seq"))
            if cfg.family == "whisper":
                out["frames"] = spec((B, cfg.encoder_seq, cfg.d_model), dt, ("batch", "seq", "embed"))
            if cfg.family == "vlm":
                out["patches"] = spec((B, cfg.vision_patches, cfg.d_model), dt, ("batch", "seq", "embed"))
            return out
        # decode: one new token against a cache of S
        return {
            "tokens": spec((B, 1), jnp.int32, ("batch", "seq")),
            "lengths": spec((B,), jnp.int32, ("batch",)),
        }


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg=cfg, mod=_FAMILY[cfg.family])

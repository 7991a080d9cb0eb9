"""Shared model layers + the logical-axis sharding system.

Sharding design (GSPMD / MaxText style): every parameter and key
activation is annotated with *logical* axis names; a rules table maps
logical names to candidate mesh axes, and ``resolve_pspec`` picks the
first candidate whose size divides the dimension (otherwise the dim is
replicated — e.g. gemma-2b's 8 attention heads on a 16-way model axis).
The mapping is mesh-aware, so the same model code runs on the single-pod
(16,16) mesh, the multi-pod (2,16,16) mesh, and a 1-device CPU test.

Parameters are declared as ``ParamDef`` trees: one declaration yields
the init fn, the PartitionSpec, and the ShapeDtypeStruct used by the
dry run, guaranteeing they never drift apart.
"""

from __future__ import annotations

import functools
import math
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..kernels import flash_attention, rmsnorm

# ---------------------------------------------------------------------------
# Logical axis rules + mesh context
# ---------------------------------------------------------------------------

# logical name -> ordered candidate mesh-axis groups; the first group whose
# total size divides the dim (and whose axes are all present in the mesh)
# is used. Entries are tuples-of-axes (one dim may span several mesh axes).
def axis_rules(cfg: ModelConfig) -> Dict[str, Sequence[Tuple[str, ...]]]:
    fsdp = cfg.sharding == "fsdp_tp"
    # tp2d (decode-oriented): big weight matrices (ff/vocab dims) shard over
    # BOTH mesh axes so they stay device-resident — no per-token FSDP
    # re-gathers; the (tiny, batch-sized) activations psum instead.
    tp2d = cfg.sharding == "tp2d"
    ff_rule = [("data", "model"), ("model",)] if tp2d else [("model",)]
    vocab_rule = [("data", "model"), ("model",)] if tp2d else [("model",)]
    sp = cfg.seq_shard_norm
    return {
        "batch": [("pod", "data"), ("data",)],
        "seq": [],                           # attention-visible seq: unsharded
        "seq_sp": [("model",)] if sp else [],   # SP: inter-block activations
        "seq_cp": [("model",)],              # context parallelism (see below)
        "embed": [],                         # activation d_model: replicated
        "heads": [("model",)],
        "heads_flat": [("model",)],          # fused (H*hd) projections (rwkv)
        "kv_heads": [("model",)],
        # tp2d: attention weights also go resident by sharding head_dim
        # over the data axis; the (tiny) q/k/v activations re-gather.
        "head_dim": [("data",)] if tp2d else [],
        "cache_kv_heads": [("model",)],      # kv cache: prefer kv-head sharding,
        "cache_seq": [("model",)],           # else shard cache length (flash-decode),
        "cache_head_dim": [("model",)],      # head_dim only for cross-attn KV
        "ff": ff_rule,
        "experts": [("model",)],
        "expert_cap": [],
        "vocab": vocab_rule,
        "embed_w": [("data",)] if fsdp else [],   # FSDP: weights' d_model dim
        "ff_w": [("model",)],
        "layers": [],
        "state": [("model",)],               # recurrent state channels
        "state2": [],                        # 2nd dim of square state matrices
        None: [],
    }


@dataclass
class MeshContext:
    mesh: Mesh
    cfg: ModelConfig
    rules: Dict[str, Sequence[Tuple[str, ...]]]


_TLS = threading.local()


def set_mesh(mesh: Optional[Mesh], cfg: ModelConfig) -> None:
    _TLS.ctx = MeshContext(mesh, cfg, axis_rules(cfg)) if mesh is not None else None


def clear_mesh() -> None:
    _TLS.ctx = None


def current_ctx() -> Optional[MeshContext]:
    return getattr(_TLS, "ctx", None)


class mesh_context:
    """``with mesh_context(mesh, cfg): ...`` — scoped sharding annotations."""

    def __init__(self, mesh: Optional[Mesh], cfg: ModelConfig) -> None:
        self.mesh, self.cfg = mesh, cfg

    def __enter__(self):
        self._prev = current_ctx()
        set_mesh(self.mesh, self.cfg)
        return self

    def __exit__(self, *exc):
        _TLS.ctx = self._prev
        return False


def resolve_pspec(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Dict[str, Sequence[Tuple[str, ...]]],
) -> P:
    """Map logical dim names to mesh axes with divisibility checking.

    Each mesh axis is used at most once per spec (GSPMD requirement)."""
    used: set = set()
    out = []
    for name, dim in zip(logical, shape):
        chosen = None
        for axes in rules.get(name, []):
            if any(a not in mesh.axis_names or a in used for a in axes):
                continue
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % total == 0 and dim >= total:
                chosen = axes
                used.update(axes)
                break
        if chosen is None:
            out.append(None)
        elif len(chosen) == 1:
            out.append(chosen[0])
        else:
            out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x: jnp.ndarray, *logical: Optional[str]) -> jnp.ndarray:
    """Apply a with_sharding_constraint from logical names (no-op w/o mesh)."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        return x
    spec = resolve_pspec(logical, x.shape, ctx.mesh, ctx.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# ParamDef system
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small_normal
    scale: float = 0.02
    dtype: Optional[str] = None  # None -> config dtype

    def initialize(self, key: jax.Array, cfg: ModelConfig) -> jnp.ndarray:
        dtype = jnp.dtype(self.dtype or cfg.dtype)
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        scale = self.scale if self.init == "normal" else self.scale * 0.1
        return _scaled_normal(key, self.shape, dtype, scale)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _scaled_normal(key: jax.Array, shape: Tuple[int, ...], dtype, scale: float) -> jnp.ndarray:
    # Jitted so the float32 draw fuses into the cast: eagerly, a stacked
    # 32-layer leaf at published width is a multi-GB float32 temporary.
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _traverse(tree: Any, fn: Callable[[ParamDef, Tuple], Any], path: Tuple = ()) -> Any:
    if isinstance(tree, ParamDef):
        return fn(tree, path)
    if isinstance(tree, dict):
        return {k: _traverse(v, fn, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_traverse(v, fn, path + (i,)) for i, v in enumerate(tree))
    raise TypeError(f"unexpected node {type(tree)} at {path}")


def init_params(defs: Any, rng: jax.Array, cfg: ModelConfig) -> Any:
    """Materialize a ParamDef tree into arrays.

    Seeding uses crc32 of the parameter path — NOT Python ``hash()``,
    which is randomized per process and would make initialization
    irreproducible across restarts/hosts."""
    import zlib

    def one(d: ParamDef, path: Tuple) -> jnp.ndarray:
        seed = zlib.crc32("/".join(map(str, path)).encode()) % (2 ** 31 - 1)
        key = jax.random.fold_in(rng, seed)
        return d.initialize(key, cfg)

    return _traverse(defs, one)


def param_pspecs(defs: Any, mesh: Mesh, cfg: ModelConfig) -> Any:
    rules = axis_rules(cfg)

    def one(d: ParamDef, path: Tuple) -> P:
        return resolve_pspec(d.logical, d.shape, mesh, rules)

    return _traverse(defs, one)


def param_shapes(defs: Any, cfg: ModelConfig) -> Any:
    def one(d: ParamDef, path: Tuple) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or cfg.dtype))

    return _traverse(defs, one)


def param_count(defs: Any) -> int:
    total = 0

    def one(d: ParamDef, path: Tuple) -> int:
        nonlocal total
        total += int(np.prod(d.shape))
        return 0

    _traverse(defs, one)
    return total


def stack_defs(layer_defs: Any, n_layers: int) -> Any:
    """Prepend a (scan) layer axis to every ParamDef in a layer tree."""

    def one(d: ParamDef, path: Tuple) -> ParamDef:
        return ParamDef(
            shape=(n_layers,) + d.shape,
            logical=("layers",) + d.logical,
            init=d.init,
            scale=d.scale,
            dtype=d.dtype,
        )

    return _traverse(layer_defs, one)


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6, offset: float = 0.0) -> jnp.ndarray:
    return rmsnorm(x, w, eps=eps, scale_offset=offset)


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (head_dim / 2,): each interpolated by
    ``factor`` or kept, with a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_len`` positions
    (the correction range truncated to whole dimensions), as
    ``transformers``' ``_compute_yarn_parameters`` computes them."""
    dim = head_dim
    extrapolation = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolation = extrapolation / factor

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_len / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)


def yarn(cfg: ModelConfig) -> Optional[Tuple[np.ndarray, float]]:
    """The full-attention layers' RoPE: YaRN's inverse frequencies and the
    scale of cos and sin, or None (plain RoPE) where ``yarn_factor`` is 0."""
    if not cfg.yarn_factor:
        return None
    freqs = yarn_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original_len,
                          cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return freqs, cfg.yarn_attention_factor


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         scaled: Optional[Tuple[np.ndarray, float]] = None) -> jnp.ndarray:
    """Rotary embedding. x: (B, S, H, D), positions: (B, S). ``scaled``
    (``yarn(cfg)``) replaces the frequencies of ``theta`` and scales cos
    and sin."""
    d = x.shape[-1]
    half = d // 2
    if scaled is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(scaled[0], jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaled is not None:
        cos, sin = cos * scaled[1], sin * scaled[1]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def embed_tokens(table: jnp.ndarray, tokens: jnp.ndarray, scale_by_dim: bool = False) -> jnp.ndarray:
    x = jnp.take(table, tokens, axis=0)
    if scale_by_dim:
        x = x * jnp.asarray(table.shape[-1] ** 0.5, x.dtype)
    return shard(x, "batch", "seq_sp", "embed")


def unembed(x: jnp.ndarray, table: jnp.ndarray, valid: Optional[int] = None) -> jnp.ndarray:
    """x: (B, S, D), table: (Vpad, D) -> logits (B, S, Vpad); rows beyond
    ``valid`` (vocab padding) are masked to -1e9 so softmax/argmax/CE
    ignore them."""
    ctx = current_ctx()
    if ctx is not None and ctx.cfg.sharding == "tp2d":
        x = shard(x, None, "seq", "embed")       # replicate tiny decode batch
        logits = jnp.einsum("bsd,vd->bsv", x, table)
        return shard(logits, None, "seq", "vocab")   # vocab -> (data, model)
    def mask_pad(logits):
        if valid is not None and valid < table.shape[0]:
            pad_mask = jnp.arange(table.shape[0]) >= valid
            logits = jnp.where(pad_mask[None, None], -1e9, logits)
        return logits

    ctx2 = current_ctx()
    if ctx2 is not None and ctx2.cfg.seq_shard_norm:
        # SP: tokens stay sequence-sharded; softmax/CE run fully local
        # (no vocab-axis collectives, logits 1/16th per device)
        x = shard(x, "batch", "seq_sp", "embed")
        logits = mask_pad(jnp.einsum("bsd,vd->bsv", x, table))
        return shard(logits, "batch", "seq_sp", None)
    logits = mask_pad(jnp.einsum("bsd,vd->bsv", x, table))
    return shard(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Attention (GQA) + MLP blocks, shared by dense/MoE/whisper/vlm families
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, d_model: Optional[int] = None, kv: Optional[int] = None) -> Dict[str, ParamDef]:
    d = d_model or cfg.d_model
    kvh = kv if kv is not None else cfg.n_kv_heads
    hd = cfg.head_dim
    return {
        "wq": ParamDef((d, cfg.n_heads, hd), ("embed_w", "heads", "head_dim")),
        "wk": ParamDef((d, kvh, hd), ("embed_w", "kv_heads", "head_dim")),
        "wv": ParamDef((d, kvh, hd), ("embed_w", "kv_heads", "head_dim")),
        "wo": ParamDef((cfg.n_heads, hd, d), ("heads", "head_dim", "embed_w")),
    }


def mlp_defs(cfg: ModelConfig, d_model: Optional[int] = None, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": ParamDef((d, f), ("embed_w", "ff")),
            "w_up": ParamDef((d, f), ("embed_w", "ff")),
            "w_down": ParamDef((f, d), ("ff", "embed_w")),
        }
    return {
        "w_up": ParamDef((d, f), ("embed_w", "ff")),
        "w_down": ParamDef((f, d), ("ff", "embed_w")),
    }


def apply_qkv(p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def context_parallel_attention(cfg: ModelConfig) -> bool:
    """True when attention heads cannot shard over the model axis (e.g.
    whisper's 20 or gemma's 8 heads on a 16-way axis): fall back to
    CONTEXT PARALLELISM — shard the query sequence dim instead, so each
    device attends 1/model_axis of the queries against (small, gathered)
    keys/values rather than replicating the whole attention."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        return False
    msize = dict(ctx.mesh.shape).get("model", 1)
    return msize > 1 and cfg.n_heads % msize != 0


def attention_block(
    cfg: ModelConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                     # (B, S, D)
    positions: jnp.ndarray,             # (B, S)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # cross-attn
    attn_kwargs: Optional[dict] = None,
) -> jnp.ndarray:
    q, k, v = apply_qkv(p, x)
    if kv_override is not None:
        k, v = kv_override
    else:
        scaled = None if window else yarn(cfg)        # YaRN on full-attention layers
        q = rope(q, positions, cfg.rope_theta, scaled)
        k = rope(k, positions, cfg.rope_theta, scaled)
    cp = context_parallel_attention(cfg)
    if cp:
        q = shard(q, "batch", "seq_cp", None, None)
    # kernels expect (B, H, S, D)
    out = flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, window=window, **(attn_kwargs or {}),
    ).swapaxes(1, 2)                     # (B, S, H, hd)
    if cp:
        out = shard(out, "batch", "seq_cp", None, None)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return shard(out, "batch", "seq_cp", "embed")
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return shard(out, "batch", "seq_sp", "embed")


def cross_attention_block(
    cfg: ModelConfig,
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                      # decoder states (B, S, D)
    enc_kv: Tuple[jnp.ndarray, jnp.ndarray],   # precomputed (B, Se, KV, hd) pairs
) -> jnp.ndarray:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = enc_kv
    if context_parallel_attention(cfg):
        q = shard(q, "batch", "seq_cp", None, None)
    out = flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=False,
    ).swapaxes(1, 2)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if context_parallel_attention(cfg):
        return shard(out, "batch", "seq_cp", "embed")
    return shard(out, "batch", "seq_sp", "embed")


def mlp_block(cfg: ModelConfig, p: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    if cfg.sharding == "tp2d":
        # decode-oriented 2D TP: weights stay resident (ff sharded over
        # data x model); the batch-replicated activations flow through and
        # the down-projection partial-sums. Worth it when B*S is tiny
        # (decode) and weights are huge — see EXPERIMENTS.md §Perf.
        x = shard(x, None, "seq", "embed")          # replicate batch
        if cfg.activation in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.activation == "swiglu" else (
                lambda t: jax.nn.gelu(t, approximate=True))
            h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
            h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
        elif cfg.activation == "relu_sq":
            h = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x, p["w_up"])))
        else:
            h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]), approximate=True)
        h = shard(h, None, "seq", "ff")              # ff -> (data, model)
        out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
        return shard(out, "batch", "seq", "embed")
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]), approximate=True)
        h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_up"]), approximate=True)
    elif cfg.activation == "relu_sq":
        h = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", x, p["w_up"])))
    else:
        raise ValueError(cfg.activation)
    h = shard(h, "batch", "seq", "ff")
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return shard(out, "batch", "seq_sp", "embed")


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(
    logits: jnp.ndarray,          # (B, S, V)
    labels: jnp.ndarray,          # (B, S) int32
    mask: Optional[jnp.ndarray] = None,   # (B, S) 1=count
    z_loss: float = 0.0,
) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    one_hot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.einsum("bsv,bsv->bs", logits, one_hot)
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()

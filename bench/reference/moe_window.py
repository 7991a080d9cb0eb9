"""Model module of the family that mixes sliding-window and full attention
and routes every MLP to experts (Mellum2): its plain float32 reference and
its counts, by the convention of ``bench/reference/__init__.py``.

Pre-norm blocks: RMSNorm, grouped-query attention with rotary positions
(the rotate-half form over the whole head), a sparse MLP, final RMSNorm
and an untied unembedding. Of each ``full_every`` layers the last attends
every earlier position, the others the last ``local_window`` positions, the
query's own included (a key at ``kv`` is seen from ``q`` where ``q - window <
kv <= q``, ``transformers``' ``sliding_window_overlay``). The full layers
rotate by YaRN's frequencies (``transformers``' ``_compute_yarn_parameters``:
each frequency interpolated by ``yarn_factor`` or kept, on a ramp between
the dimensions that turn ``yarn_beta_fast`` and ``yarn_beta_slow`` times over
``yarn_original_len`` positions, truncated to whole dimensions) with cos and
sin scaled by ``yarn_attention_factor``; the sliding layers by plain RoPE at
``rope_theta``.

The router scores all ``router_experts`` experts in float32, takes the
softmax, keeps the top ``experts_per_token`` and renormalises them. The
layer holds ``n_experts`` of them, from ``first_expert`` on: each held
expert's SwiGLU output is added with its gate, and the experts held
elsewhere add nothing. Every token reaches every expert it routed to
(no capacity).

One sequence at a time, the whole sequence at once; no kernels, no cache,
no batching; matrix products at ``jax.default_matmul_precision("highest")``.
It reads the benchmark's bf16 weights and upcasts one layer at a time.
``mode="fp8"`` is the control: every operand of every matrix product, the
router's included, rounded to float8 e4m3 with one scale per tensor.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import BUCKET, _gaps, _mm, _norm, _table


def _sliding(cfg: Dict[str, Any], i: int) -> bool:
    return bool(cfg["full_every"]) and (i + 1) % cfg["full_every"] != 0


def attended(cfg: Dict[str, Any], length: int) -> List[int]:
    """Positions that a slot of valid length ``length`` attends, in each
    layer, in layer order: ``min(length, window)`` in a sliding layer."""
    return [min(length, cfg["local_window"]) if _sliding(cfg, i) else length
            for i in range(cfg["n_layers"])]


def token_flops(cfg: Dict[str, Any], context: int, served: bool) -> int:
    """One token's operations on this chip: two per weight of the attention
    projections and of the router, the routed experts that this chip holds
    (``experts_per_token x held / router width`` of them, three matrices
    each), 4 x heads x head size per position attended, and the
    unembedding where ``served``."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    routed = cfg["experts_per_token"] * cfg["n_experts"] / cfg["router_experts"]
    weights = d * h * hd + 2 * d * kv * hd + h * hd * d + d * cfg["router_experts"] \
        + routed * 3 * d * cfg["d_ff"]
    attention = sum(4 * h * hd * n for n in attended(cfg, context))
    unembed = 2 * d * cfg["vocab_size"] if served else 0
    return int(cfg["n_layers"] * 2 * weights + attention + unembed)


def yarn_inv_freq(cfg: Dict[str, Any]) -> np.ndarray:
    """The full layers' inverse frequencies, (head_dim / 2,)."""
    dim, base, factor = cfg["head_dim"], cfg["rope_theta"], cfg["yarn_factor"]
    plain = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def turns_to_dim(turns):
        return dim * math.log(cfg["yarn_original_len"] / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_to_dim(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(cfg["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    interpolated = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return plain / factor * interpolated + plain * (1 - interpolated)


def _rope(x, inv_freq, scale):
    s, _, d = x.shape
    half = d // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def window_mask(s: int, window: int) -> jnp.ndarray:
    """(q, kv) True where query ``q`` sees key ``kv``: causal, and within
    ``window`` of it where ``window`` > 0."""
    q, kv = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    return (kv <= q) & ((kv > q - window) if window else True)


@functools.partial(jax.jit, static_argnames=("cfg", "mode", "sliding"))
def _layer(x, layers, i, *, cfg, mode, sliding):
    c = dict(cfg)
    lp = jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), layers)
    at, ff = lp["attn"], lp["ffn"]
    s = x.shape[0]
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    if sliding or not c["yarn_factor"]:
        inv_freq, scale = 1.0 / c["rope_theta"] ** (np.arange(0, hd, 2) / hd), 1.0
    else:
        inv_freq, scale = yarn_inv_freq(c), c["yarn_attention_factor"]
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    y = _norm(x, lp["ln1"]["w"], c["norm_eps"])
    q = _rope(_mm("sd,dhk->shk", y, at["wq"], mode), inv_freq, scale)
    k = _rope(_mm("sd,dhk->shk", y, at["wk"], mode), inv_freq, scale)
    v = _mm("sd,dhk->shk", y, at["wv"], mode)
    q = q.reshape(s, kv, h // kv, hd)
    scores = _mm("qkgd,tkd->kgqt", q, k, mode) * hd ** -0.5
    seen = window_mask(s, c["local_window"] if sliding else 0)
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    o = _mm("kgqt,tkd->qkgd", p, v, mode).reshape(s, h, hd)
    x = x + _mm("shk,hkd->sd", o, at["wo"], mode)

    y = _norm(x, lp["ln2"]["w"], c["norm_eps"])
    probs = jax.nn.softmax(_mm("sd,de->se", y, ff["router"], mode), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, c["experts_per_token"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    held = jax.nn.one_hot(top_i - c["first_expert"], c["n_experts"])      # 0 where held elsewhere
    gates = jnp.einsum("sk,ske->se", top_p, held)
    g = jax.nn.silu(_mm("sd,edf->esf", y, ff["w_gate"], mode)) * _mm("sd,edf->esf", y, ff["w_up"], mode)
    return x + jnp.einsum("se,esd->sd", gates, _mm("esf,efd->esd", g, ff["w_down"], mode))


def hidden(cfg: Dict[str, Any], params: Any, tokens: np.ndarray, mode: str = "float32") -> jnp.ndarray:
    """Final-normed hidden states (S_padded, D) of one sequence."""
    s = len(tokens)
    padded = np.zeros(-(-s // BUCKET) * BUCKET, np.int32)
    padded[:s] = tokens
    frozen = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
        for i in range(cfg["n_layers"]):
            x = _layer(x, params["layers"], i, cfg=frozen, mode=mode, sliding=_sliding(cfg, i))
        return _norm(x, params["final_norm"]["w"], cfg["norm_eps"])


def served_gaps(cfg: Dict[str, Any], params: Any, prompt: np.ndarray, served: np.ndarray,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps of every served token of one request, and of the control's.

    The served token ``served[j]`` was produced at position ``P - 1 + j``,
    from the prompt and the tokens served before it."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = np.arange(len(prompt) - 1, len(seq))
    h_ref = hidden(cfg, params, seq)
    h_ctl = hidden(cfg, params, seq, mode="fp8") if control else h_ref
    target = np.zeros(h_ref.shape[0], np.int32)
    target[pos] = served
    with jax.default_matmul_precision("highest"):
        g, c = _gaps(h_ref, h_ctl, _table(cfg, params), jnp.asarray(target),
                     vocab=int(cfg["vocab_size"]), ctl_mode="fp8" if control else None)
    g, c = np.asarray(g)[pos], np.asarray(c)[pos]
    return {"served": g, "control": c if control else None}

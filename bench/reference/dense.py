"""Model module of the dense decoder family (phi4-mini, yi): its plain
float32 reference and its counts, by the convention of
``bench/reference/__init__.py``.

Pre-norm blocks: RMSNorm, grouped-query attention with rotary positions
(the rotate-half form over the whole head), SwiGLU MLP, final RMSNorm and
an unembedding that is the embedding table when the configuration ties
them. One sequence at a time, the whole sequence at once, causal; no
kernels, no cache, no batching. Matrix products run at
``jax.default_matmul_precision("highest")``, so they are float32 on a TPU
too. The sizes, ``norm_eps`` and ``rope_theta`` come from the
configuration file; the departures of the served model from the published
one are listed there, and this reference follows the served equations.

It reads the weights the benchmark made (bf16, in the program's tree
layout) and upcasts one layer at a time. ``mode="fp8"`` is the control:
the same computation with every operand of every matrix product rounded to
float8 e4m3 with one scale per tensor.

Every layer runs the decode-attention kernel over the whole valid length,
and a token's operations are ``bench/flops.py``'s.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import token_flops  # noqa: F401  (this family's count, re-exported)

BUCKET = 512            # sequences are padded to a multiple of this
VOCAB_BLOCK = 8192      # unembedding rows per block


def attended(cfg: Dict[str, Any], length: int) -> List[int]:
    """Positions that a slot of valid length ``length`` attends, in each layer."""
    return [length] * cfg["n_layers"]


def _q(x: jnp.ndarray, mode: str) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    if mode == "float32":
        return x
    scale = jnp.maximum(jnp.abs(x).max(), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, mode: str):
    return jnp.einsum(spec, _q(a, mode), _q(b, mode))


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(x, layers, i, *, cfg, mode):
    c = dict(cfg)
    lp = jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), layers)
    at, ff = lp["attn"], lp["ffn"]
    s = x.shape[0]
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    y = _norm(x, lp["ln1"]["w"], c["norm_eps"])
    q = _rope(_mm("sd,dhk->shk", y, at["wq"], mode), c["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", y, at["wk"], mode), c["rope_theta"])
    v = _mm("sd,dhk->shk", y, at["wv"], mode)
    q = q.reshape(s, kv, h // kv, hd)
    scores = _mm("qkgd,tkd->kgqt", q, k, mode) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("kgqt,tkd->qkgd", p, v, mode).reshape(s, h, hd)
    x = x + _mm("shk,hkd->sd", o, at["wo"], mode)
    y = _norm(x, lp["ln2"]["w"], c["norm_eps"])
    g = jax.nn.silu(_mm("sd,df->sf", y, ff["w_gate"], mode)) * _mm("sd,df->sf", y, ff["w_up"], mode)
    return x + _mm("sf,fd->sd", g, ff["w_down"], mode)


def hidden(cfg: Dict[str, Any], params: Any, tokens: np.ndarray, mode: str = "float32") -> jnp.ndarray:
    """Final-normed hidden states (S_padded, D) of one sequence."""
    s = len(tokens)
    padded = np.zeros(-(-s // BUCKET) * BUCKET, np.int32)
    padded[:s] = tokens
    frozen = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(padded), axis=0).astype(jnp.float32)
        for i in range(cfg["n_layers"]):
            x = _layer(x, params["layers"], i, cfg=frozen, mode=mode)
        return _norm(x, params["final_norm"]["w"], cfg["norm_eps"])


def _table(cfg: Dict[str, Any], params: Any) -> jnp.ndarray:
    return params["embed"] if cfg["tie_embeddings"] else params["unembed"]


@functools.partial(jax.jit, static_argnames=("vocab", "ctl_mode"))
def _gaps(h_ref, h_ctl, table, served, *, vocab, ctl_mode):
    """Per position: the reference's best logit minus its logit of the
    served token, and minus its logit of the token the control ranks first."""
    n_blocks = -(-vocab // VOCAB_BLOCK)
    pad = n_blocks * VOCAB_BLOCK - table.shape[0]
    tab = jnp.pad(table, ((0, max(pad, 0)), (0, 0)))[: n_blocks * VOCAB_BLOCK]
    tab = tab.reshape(n_blocks, VOCAB_BLOCK, -1)
    s = h_ref.shape[0]

    def body(carry, blk):
        best, at_served, ctl_best, ctl_ref, b = carry
        w = blk.astype(jnp.float32)
        ids = b * VOCAB_BLOCK + jnp.arange(VOCAB_BLOCK)
        valid = ids < vocab
        ref = jnp.where(valid, h_ref @ w.T, -jnp.inf)
        ctl = ref if ctl_mode is None else jnp.where(valid, _mm("sd,vd->sv", h_ctl, w, ctl_mode),
                                                     -jnp.inf)
        best = jnp.maximum(best, ref.max(-1))
        hit = ids[None, :] == served[:, None]
        at_served = at_served + jnp.where(hit, ref, 0.0).sum(-1)
        j = ctl.argmax(-1)
        c_val = jnp.take_along_axis(ctl, j[:, None], -1)[:, 0]
        r_val = jnp.take_along_axis(ref, j[:, None], -1)[:, 0]
        take = c_val > ctl_best
        return (best, at_served, jnp.where(take, c_val, ctl_best),
                jnp.where(take, r_val, ctl_ref), b + 1), None

    init = (jnp.full((s,), -jnp.inf), jnp.zeros((s,)), jnp.full((s,), -jnp.inf),
            jnp.zeros((s,)), jnp.int32(0))
    (best, at_served, _, ctl_ref, _), _ = jax.lax.scan(body, init, tab)
    return best - at_served, best - ctl_ref


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

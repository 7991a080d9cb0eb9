"""Names of the ``jax.named_scope`` regions of the single-token decode step.

XLA copies each scope into the ``op_name`` metadata of the instructions
it lowers to, so a profile of the compiled step can charge device time
to a part of the model by these names, whatever number a fusion gets.
They are metadata only: the compiled program is the same without them.

=================  ==========================================================
``embed``          the token-embedding lookup
``layers``         the scan over the layers, which carries the stacked
                   cache; what no inner scope claims, such as the norms,
                   stays here
``attn``           q/k/v projections, RoPE, the decode-attention kernel and
                   the output projection
``kv_cache.update``  ``kvcache.update_cache``: the new token's k and v
                   written in place into the layer's part of the cache
``mlp``            the feed-forward block
``moe.router``     in ``mlp`` of an expert layer: the router's scores, its
                   top-k and the gates of the experts held here
``moe.experts``    in ``mlp`` of an expert layer: the held experts' MLPs
                   and the gated sum of their outputs
``unembed``        the final norm and the logits
``sample``         the next token (argmax or sampling) and the finiteness
                   check of the logits
=================  ==========================================================
"""

EMBED = "embed"
LAYERS = "layers"
ATTN = "attn"
KV_CACHE_UPDATE = "kv_cache.update"
MLP = "mlp"
UNEMBED = "unembed"
SAMPLE = "sample"
MOE_ROUTER = "moe.router"
MOE_EXPERTS = "moe.experts"

DECODE_SCOPES = (EMBED, LAYERS, ATTN, KV_CACHE_UPDATE, MLP, UNEMBED, SAMPLE)

"""Model modules: one per family of configurations, named by a
configuration file's ``"reference"`` key and found by that name as
``bench/reference/<name>.py``. ``bench/run.py`` loads the cell's module,
uses its reference to decide ``correct`` and hands it to the metric
readers as ``Run.reference``. A module exports three functions; ``cfg``
is the configuration file's ``model`` object, every key of it, as the
program runs it (``bench/run.py`` ``model_dict``):

- ``served_gaps(cfg, params, prompt, served, control=False)``: the plain
  reference, which imports nothing of the program. It runs over the prompt
  and the served tokens and returns, for each served token, its best logit
  less its logit of that token (``"served"``), and with ``control`` the
  same gap of the token that the lower-precision control ranks first
  (``"control"``, else None).
- ``token_flops(cfg, context, served)``: the model operations that one
  token needs on this chip when it stands at ``context`` valid positions:
  two per weight of every matrix product it goes through, and 4 x heads x
  head size per position that each attention layer attends; the
  unembedding only where ``served``. ``step.mfu`` sums it over the
  window's tokens.
- ``attended(cfg, length)``: one entry per layer that runs the
  decode-attention kernel, in the order they run: the positions that a
  slot of valid length ``length`` attends there. ``len(attended(cfg, 1))``
  is the kernel's calls per serve-step call. ``decode_attention_roofline``
  charges each call at these lengths.

What a later family's module states in them:

- routed experts: the experts a token runs on this chip,
  ``experts_per_token x held / router width``, where ``held`` is the
  experts this chip holds and the router width the experts routed over,
  each counted at its three matrices, with the router's own product;
- shared experts: every one of them, for every token, on top;
- sliding-window layers: ``min(length, window)`` positions, in
  ``attended`` and in ``token_flops``'s attention term alike; full layers
  attend ``length``;
- layers that run no decode-attention kernel (recurrent blocks) are left
  out of ``attended``.
"""

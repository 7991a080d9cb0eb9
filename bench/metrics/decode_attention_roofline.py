"""Share of its roofline that the Pallas decode-attention kernel reaches in
the traced window.

The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s, summed over the calls that the trace shows, with
``bench/kernels/decode_attention.py``'s count of what each call needs: the
useful slots at the lengths they attend. The kernel runs once in each
layer that the configuration's model module's ``attended`` lists, in each
serve-step call, and a slot of length ``n`` attends ``attended(cfg, n)[l]``
positions in the ``l``-th of them (``bench/reference/``). A decode call
serves the slots that got a token in it, at the lengths the benchmark's
hooks saw. The calls beyond those fed a prompt token to one slot; each is
charged at the shortest prompt positions the window's admissions had, so
the least time stays a floor whatever the engine's prefill does. The bytes
bound it. The kernel's time is the device time of its calls in the trace."""

from collections import Counter, defaultdict

from bench.kernels import decode_attention as kernel


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s(kernel.NAME)
    cfg, attended = run.model, run.reference.attended
    n_attending = len(attended(cfg, 1))
    calls = run.trace.kernel_calls(kernel.NAME) // n_attending if n_attending else 0
    if not t or not calls:
        return None
    steps = run.window_steps()
    decode = [s.decode for s in steps if s.decode][:calls]
    prefill = sorted(n for s in steps for n in s.prefill)[:calls - len(decode)]
    # least time of one layer's call, summed by how many layers of a call
    # attend those same lengths: one entry, n_layers, where all layers do
    least = defaultdict(float)
    for lengths in decode + [[n] for n in prefill]:
        by_layer = Counter(zip(*(attended(cfg, n) for n in lengths)))
        for layer_lengths, layers in by_layer.items():
            ops, nbytes = kernel.cost(layer_lengths, cfg["n_heads"], cfg["n_kv_heads"],
                                      cfg["head_dim"])
            least[layers] += max(ops / run.peaks["flops_per_s"]["bfloat16"],
                                 nbytes / run.peaks["hbm_bytes_per_s"])
    share = sum(100.0 * layers * s for layers, s in least.items()) / t
    return share if share else None

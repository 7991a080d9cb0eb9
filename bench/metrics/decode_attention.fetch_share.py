"""Share of the KV cache's blocks that the decode-attention kernel fetched
over the window's decode steps: the change of
``EngineStats.kv_blocks_read`` over the change of ``kv_blocks_held``.

The program works both out in each decode step from the step's lengths
with the kernel's own block and clamp (``kvcache.kv_blocks``), summed over
the layers that attend a cache (full layers at the slot's length,
sliding-window rings at ``min(length, window)``): an analytic count of the
index maps' rule, not an observation of the kernel's DMAs. The serve-step
calls that fed prompt tokens are not counted. A program without the
counters reads None."""


def read(run):
    def change(name):
        return getattr(run.stats_close, name, 0) - getattr(run.stats_open, name, 0)

    held = change("kv_blocks_held")
    return 100.0 * change("kv_blocks_read") / held if held else None

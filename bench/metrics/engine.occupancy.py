"""Mean share of the engine's slots that a decode step serves, over the window:
the change of ``EngineStats.batch_occupancy_sum`` over the change of ``steps``."""


def read(run):
    steps = run.stats_close.steps - run.stats_open.steps
    occ = run.stats_close.batch_occupancy_sum - run.stats_open.batch_occupancy_sum
    return 100.0 * occ / steps if steps else None

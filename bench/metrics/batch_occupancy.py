"""batch_occupancy: scheduler, active slots over max_batch, meaned over
the window's decode ticks (%)."""


def read(run):
    ticks = [n for t0, _, n in run.ticks if t0 >= run.t0]
    if not ticks:
        return None
    return 100.0 * sum(ticks) / (len(ticks) * run.max_batch)

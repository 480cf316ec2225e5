"""Dispatches the page pool took to be brought up to a batch, a fused step:
the program's ``pool_dispatches`` counters over the window (``group_writes``
of up to sixteen new rows each, and ``gathers``, one a crossing of the row
bucket) over ``batch.steps``. A program without the counters has nothing to
read."""


def read(stats, spans, trace, cell):
    pool = stats.get("pool_dispatches")
    steps = stats.get("batch", {}).get("steps")
    if not pool or not steps:
        return None
    return (pool["group_writes"] + pool["gathers"]) / steps

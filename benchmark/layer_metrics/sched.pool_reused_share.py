"""Page-pool rows a fused step found already on the device, as a share of
the rows its batches referenced: the program's pool counters over the
window (``rows_reused`` kept their row; ``rows_written`` were written in
place or into a new pool). A program without the counters has nothing
to read."""


def read(stats, spans, trace, cell):
    pool = stats.get("pool")
    if not pool:
        return None
    rows = pool["rows_reused"] + pool["rows_written"]
    if not rows:
        return None
    return 100.0 * pool["rows_reused"] / rows

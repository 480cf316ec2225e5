"""Seats filled per fused step, as a share of ``max_batch``: the program's
batch counters over the window."""


def read(stats, spans, trace, cell):
    steps = stats["batch"]["steps"]
    if not steps:
        return None
    seats = cell["traffic"]["engine"]["max_batch"]
    return 100.0 * stats["batch"]["size_sum"] / (steps * seats)

"""Device time of the fused decode step's program per execution, from the
trace's ``XLA Modules`` line."""

PROGRAM = "paged_decode_batch_step"


def read(stats, spans, trace, cell):
    if trace is None:
        return None
    count, total = cell["lib"]["trace_reduce"].program(trace, PROGRAM)
    if not count:
        return None
    return 1e3 * total / count

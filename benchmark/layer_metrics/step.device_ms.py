"""Device time of the fused decode step's program per execution, from the
trace's ``XLA Modules`` line. The program's name is the cell's family's
(``families/<family>.py::DECODE_STEP_PROGRAM``)."""


def read(stats, spans, trace, cell):
    if trace is None:
        return None
    count, total = cell["lib"]["trace_reduce"].program(
        trace, cell["lib"]["family"].DECODE_STEP_PROGRAM)
    if not count:
        return None
    return 1e3 * total / count

"""The least time the chip could take for a fused decode step (memory
bound: the weights once and the seated sessions' context K and V once, over
the chip's peak HBM bandwidth) as a share of the step's device time.
Bytes from the cell's family's bytes model (``bytes_models/<name>.py``) and
the program's name from its adapter (``DECODE_STEP_PROGRAM``); context
positions per step are the window's mean (every generated token's context
length, as the clients count it, over the fused steps); peak from
``peaks.json``; time from the trace."""


def read(stats, spans, trace, cell):
    steps = stats["batch"]["steps"]
    if trace is None or not steps:
        return None
    count, total = cell["lib"]["trace_reduce"].program(
        trace, cell["lib"]["family"].DECODE_STEP_PROGRAM)
    if not count:
        return None
    ctx = cell["window"]["context_tokens"] / steps
    least_s = (cell["lib"]["bytes_model"].decode_step_bytes(cell["config"], ctx)
               / cell["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (total / count)

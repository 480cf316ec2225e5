"""The least time the chip could take for the fused steps as they were
routed (memory bound: the fixed weights, the distinct experts the steps
counted, the seated sessions' latent context, over peak HBM bandwidth) as a
share of the step's device time. ``step.roofline_share`` holds the same
time to the least any routing allows; this one to the bytes of the routing
that happened. Bytes from the family's bytes model
(``step_bytes_counted``), the program's name from its adapter, expert rows
from the program's ``moe.step_expert_rows`` counter, time from the trace."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    steps = stats["batch"]["steps"]
    counted = getattr(cell["lib"]["bytes_model"], "step_bytes_counted", None)
    if trace is None or not moe or not steps or counted is None:
        return None
    count, total = cell["lib"]["trace_reduce"].program(
        trace, cell["lib"]["family"].DECODE_STEP_PROGRAM)
    if not count:
        return None
    least_s = counted(
        cell["config"], cell["window"]["context_tokens"] / steps,
        moe["step_expert_rows"] / steps) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

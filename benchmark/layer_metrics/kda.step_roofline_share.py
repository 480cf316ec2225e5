"""The least time the chip could take for the fused steps of a family with
a recurrent carry as they were routed and seated (memory bound: the fixed
weights, the distinct held experts the steps counted, the seated sessions'
latent context, and every seat's carry read and written, over peak HBM
bandwidth) as a share of the step's device time. Bytes from the family's
bytes model (``step_bytes_counted`` with a ``seats`` argument, which only a
family with a carry has), the program's name from its adapter, expert rows
from the program's ``moe.step_expert_rows`` counter, seats from
``batch.size_sum``, time from the trace. A program without the ``carry``
counters (another family, a parent that lacks them) reports nothing."""


def read(stats, spans, trace, cell):
    moe, carry = stats.get("moe"), stats.get("carry")
    steps = stats["batch"]["steps"]
    counted = getattr(cell["lib"]["bytes_model"], "step_bytes_counted", None)
    has_carry = hasattr(cell["lib"]["bytes_model"], "carry_bytes")
    if (trace is None or not moe or not carry or not steps
            or counted is None or not has_carry):
        return None
    count, total = cell["lib"]["trace_reduce"].program(
        trace, cell["lib"]["family"].DECODE_STEP_PROGRAM)
    if not count:
        return None
    least_s = counted(
        cell["config"], cell["window"]["context_tokens"] / steps,
        moe["step_expert_rows"] / steps,
        stats["batch"]["size_sum"] / steps) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

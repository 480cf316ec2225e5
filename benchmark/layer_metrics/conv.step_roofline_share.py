"""The least time the chip could take for the fused steps of the
``conv_gqa_moe`` family as they were routed and seated (memory bound: the
fixed weights, the distinct experts the steps counted, K and V of the seated
sessions' contexts in the attention layers, and every seat's carry read and
written, over peak HBM bandwidth) as a share of the step's device time.
Bytes from the family's bytes model (``step_bytes_counted`` with a ``seats``
argument and ``kv_bytes_per_token`` over ``attn_layers``, which only this
family's has), the program's name from its adapter, expert rows from the
program's ``moe.step_expert_rows`` counter, seats from ``batch.size_sum``,
time from the trace. Another family, or a program without the counters,
reports nothing."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    steps = stats.get("batch", {}).get("steps")
    bm = cell["lib"]["bytes_model"]
    counted = getattr(bm, "step_bytes_counted", None)
    if (trace is None or not moe or not steps or counted is None
            or not hasattr(bm, "carry_bytes")
            or not hasattr(bm, "conv_layers")):
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

"""The least time the chip could take for the fused steps of any family
whose bytes model counts a step by what it read (``step_bytes_counted``
over ``layer_position_bytes``: the fixed weights, the distinct experts the
steps counted, the (layer, position) pairs the seated sessions' live pages
held and each seat's tails, over peak HBM bandwidth) as a share of the
step's device time. One reader for every such family, where ``moe.*``,
``swa.*``, ``kda.*`` and ``conv.*`` each read their own (PERF.md section
7). The program's name from the family's adapter, expert rows from the
program's ``moe.step_expert_rows`` counter, pairs from
``kv.positions_held``, seats from ``batch.size_sum``, time from the trace.
A program without those counters, or a family whose bytes model lacks
those functions, reports nothing."""


def read(stats, spans, trace, cell):
    moe, kv = stats.get("moe"), stats.get("kv")
    steps = stats["batch"]["steps"]
    bm = cell["lib"]["bytes_model"]
    if (trace is None or not moe or not kv or not steps
            or not hasattr(bm, "step_bytes_counted")
            or not hasattr(bm, "layer_position_bytes")):
        return None
    count, total = cell["lib"]["trace_reduce"].program(
        trace, cell["lib"]["family"].DECODE_STEP_PROGRAM)
    if not count:
        return None
    least_s = bm.step_bytes_counted(
        cell["config"], kv["positions_held"] / steps,
        moe["step_expert_rows"] / steps, stats["batch"]["size_sum"] / steps,
        cell["traffic"]["engine"]["page_tokens"],
    ) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

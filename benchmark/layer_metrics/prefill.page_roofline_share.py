"""The least time the chip could take for a page program (one page of
prompt through every layer: memory bound, the fixed weights and the
distinct experts the pages counted, over peak HBM bandwidth) as a share of
its device time. The context's latent is left out of the bytes (under a
thousandth of the weights at these prompts), so the share reads a little
low, never high. Bytes from the family's bytes model
(``page_bytes_counted``), the program's name from its adapter
(``PREFILL_PAGE_PROGRAM``), expert rows from the program's
``moe.page_expert_rows`` and ``moe.page_count`` counters, time from the
trace."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    name = getattr(cell["lib"]["family"], "PREFILL_PAGE_PROGRAM", None)
    counted = getattr(cell["lib"]["bytes_model"], "page_bytes_counted", None)
    if (trace is None or not moe or not moe["page_count"] or name is None
            or counted is None):
        return None
    count, total = cell["lib"]["trace_reduce"].program(trace, name)
    if not count:
        return None
    least_s = counted(
        cell["config"], 0.0, moe["page_expert_rows"] / moe["page_count"],
    ) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

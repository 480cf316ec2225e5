"""The least time the chip could take for a page program of a family whose
cache has a full and a sliding-window kind (one page of prompt through every
layer: memory bound, the fixed weights, the distinct held experts the pages
counted and one session's tails, over peak HBM bandwidth) as a share of its
device time. The context's K and V are left out of the bytes (no counter
says how long a chunk's context was; at most 27 MB against 1.6 GB of fixed
weights), so the share reads a little low, never high. Bytes from the
family's bytes model (``page_bytes_counted``; only a family with
``layer_position_bytes`` is read), the program's name from its adapter
(``PREFILL_PAGE_PROGRAM``), expert rows from the program's
``moe.page_expert_rows`` and ``moe.page_count`` counters, time from the
trace."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    bm = cell["lib"]["bytes_model"]
    name = getattr(cell["lib"]["family"], "PREFILL_PAGE_PROGRAM", None)
    if (trace is None or not moe or not moe["page_count"] or name is None
            or not hasattr(bm, "layer_position_bytes")):
        return None
    count, total = cell["lib"]["trace_reduce"].program(trace, name)
    if not count:
        return None
    least_s = bm.page_bytes_counted(
        cell["config"], 0.0, moe["page_expert_rows"] / moe["page_count"],
        cell["traffic"]["engine"]["page_tokens"],
    ) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

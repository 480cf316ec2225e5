"""The least time the chip could take for a page program of the
``conv_gqa_moe`` family (one page of prompt through every layer: memory
bound, the fixed weights, the distinct experts the pages counted and one
session's carry read and written, over peak HBM bandwidth) as a share of its
device time. The context's K and V are left out of the bytes (at most 10 MB
of a 2.5k-token context against gigabytes of weights), so the share reads a
little low, never high. Bytes from the family's bytes model
(``page_bytes_counted``; only a bytes model with ``conv_layers`` is read),
the program's name from its adapter (``PREFILL_PAGE_PROGRAM``), expert rows
from the program's ``moe.page_expert_rows`` and ``moe.page_count`` counters,
time from the trace."""


def read(stats, spans, trace, cell):
    moe = stats.get("moe")
    bm = cell["lib"]["bytes_model"]
    name = getattr(cell["lib"]["family"], "PREFILL_PAGE_PROGRAM", None)
    counted = getattr(bm, "page_bytes_counted", None)
    if (trace is None or not moe or not moe.get("page_count") or name is None
            or counted is None or not hasattr(bm, "conv_layers")):
        return None
    count, total = cell["lib"]["trace_reduce"].program(trace, name)
    if not count:
        return None
    least_s = counted(
        cell["config"], 0.0, moe["page_expert_rows"] / moe["page_count"],
    ) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

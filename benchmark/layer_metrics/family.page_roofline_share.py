"""The least time the chip could take for a page program (one page of
prompt through every layer: memory bound, the fixed weights, the distinct
experts the pages counted, the (layer, position) pairs of the context each
was handed and one session's tails, over peak HBM bandwidth) as a share of
its device time, for any family whose bytes model offers
``page_bytes_counted`` and ``layer_position_bytes``. The context comes from
the program's ``kv.page_positions_read`` counter (every earlier position of
a full layer, at most a window's of a sliding one), which
``swa.page_roofline_share`` had no counter for. The program's name from the
adapter (``PREFILL_PAGE_PROGRAM``), expert rows from ``moe.page_expert_rows``
and ``moe.page_count``, time from the trace. A program without the
``page_positions_read`` counter (a parent that lacks it), or a family whose
bytes model lacks those functions, reports nothing."""


def read(stats, spans, trace, cell):
    moe, kv = stats.get("moe"), stats.get("kv")
    bm = cell["lib"]["bytes_model"]
    name = getattr(cell["lib"]["family"], "PREFILL_PAGE_PROGRAM", None)
    if (trace is None or not moe or not moe["page_count"] or name is None
            or not kv or "page_positions_read" not in kv
            or not hasattr(bm, "page_bytes_counted")
            or not hasattr(bm, "layer_position_bytes")):
        return None
    count, total = cell["lib"]["trace_reduce"].program(trace, name)
    if not count:
        return None
    pages = moe["page_count"]
    least_s = bm.page_bytes_counted(
        cell["config"], kv["page_positions_read"] / pages,
        moe["page_expert_rows"] / pages,
        cell["traffic"]["engine"]["page_tokens"],
    ) / cell["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (total / count)

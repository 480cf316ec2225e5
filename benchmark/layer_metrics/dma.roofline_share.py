"""Bytes the page-moving DMA kernels (row write, row read, local copy of
``ops/pallas_ici.py``) read and wrote, over their device time, as a share of
the chip's peak HBM bandwidth. Each execution moves one page: it is read
once and written once. The three kernels are jitted under one name
(``jit_run``), so the trace cannot tell them apart yet."""

PROGRAM = "jit_run"


def read(stats, spans, trace, cell):
    if trace is None:
        return None
    rec = trace["programs"].get(PROGRAM)
    if not rec or not rec["count"] or not rec["total_s"]:
        return None
    moved = rec["count"] * cell["lib"]["bytes_shared"].page_copy_bytes(
        cell["page_bytes"])
    return 100.0 * moved / rec["total_s"] / cell["peak"]["hbm_bytes_per_s"]

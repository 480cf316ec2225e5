"""Share of the slowest 5 % of the window's token gaps (``itl.hist``, by
engine seconds) that was prefill chunks (``serve_prefill_chunk``: every
session's chunk of the ticks the gap spanned, its ship and drop included):
``chunk_s`` over ``sum_s``. A program without the histogram has nothing to
read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    return _lib["share_of"](stats.get("itl", {}).get("hist"), 0.05,
                            "chunk_s", "sum_s")

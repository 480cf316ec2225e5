"""Ticks from admission to the first token, a request, over the slowest 10 %
of the window's first tokens (``ttft.tail_hist``, by engine seconds since
admission). A program without the histogram has nothing to read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    return _lib["per_entry"](stats.get("ttft", {}).get("tail_hist"), 0.10,
                             "ticks")

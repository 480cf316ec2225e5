"""Ticks a gap spanned, over the slowest 5 % of the window's token gaps
(``itl.hist``, by engine seconds): 1.0 is a session seated in every tick;
more, ticks it stood without a seat, yielded, or whose fused step it was not
in. A program without the histogram has nothing to read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    return _lib["per_entry"](stats.get("itl", {}).get("hist"), 0.05, "ticks")

"""Share of the ticks from admission to the first token in which the request
was runnable and had no seat, over the slowest 10 % of the window's first
tokens (``ttft.tail_hist``): ``unseated_ticks`` over ``ticks``, the wait for
a seat. A program without the histogram has nothing to read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    return _lib["share_of"](stats.get("ttft", {}).get("tail_hist"), 0.10,
                            "unseated_ticks", "ticks")

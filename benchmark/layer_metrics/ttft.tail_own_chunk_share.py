"""Share of the engine seconds from admission to the first token that was the
request's own prefill chunks, over the slowest 10 % of the window's first
tokens (``ttft.tail_hist``): ``own_chunk_s`` over ``sum_s``; the rest is
standing behind other sessions' ticks. A program without the histogram has
nothing to read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    return _lib["share_of"](stats.get("ttft", {}).get("tail_hist"), 0.10,
                            "own_chunk_s", "sum_s")

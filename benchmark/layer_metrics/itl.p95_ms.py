"""The engine's own 95th gap between two tokens of a session, tick end to
tick end less the caller's time, interpolated in the program's gap histogram
(``itl.hist``) over the window: it stands beside the client's ``itl_ms_p95``
and says whether the two clocks agree. A program without the histogram has
nothing to read."""

import os
import runpy

_lib = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "tail_hist.py"))


def read(stats, spans, trace, cell):
    gap = _lib["quantile"](stats.get("itl", {}).get("hist"), 0.95)
    return None if gap is None else 1e3 * gap

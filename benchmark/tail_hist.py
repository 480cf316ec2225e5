"""The slow end of one of the program's histograms with parts
(``ServingStats.snapshot()["itl"]["hist"]``, ``["ttft"]["tail_hist"]``):
``{upper bound in seconds: {"count", "sum_s", ...}}``, a bucket adding up
what its entries were made of, the window's after ``harness.delta``. Shared
by the readers ``layer_metrics/itl.*.py`` and ``layer_metrics/ttft.tail_*.py``,
which run this file by its path (``runpy.run_path``: a copy of the benchmark
runs its own)."""

# The program's buckets are at most this far apart (``metrics.GAP_BUCKETS``,
# a quarter of an octave): where a bucket's lower neighbour is empty, its
# own lower edge is taken to be this far below its bound.
RATIO = 2.0 ** 0.25


def _filled(hist) -> list:
    """The buckets that hold something, by rising bound."""
    return sorted((float(bound), b) for bound, b in (hist or {}).items()
                  if b["count"] > 0)


def slowest(hist, share: float) -> dict | None:
    """Every field summed over the slowest ``share`` of the entries: whole
    buckets from the top down, the bucket the boundary falls in pro rata.
    None where the histogram holds nothing."""
    buckets = _filled(hist)
    if not buckets:
        return None
    want = share * sum(b["count"] for _, b in buckets)
    out = dict.fromkeys(buckets[0][1], 0.0)
    for _, b in reversed(buckets):
        take = min(1.0, want / b["count"])
        for field, v in b.items():
            out[field] += take * v
        want -= take * b["count"]
        if want <= 0:
            break
    return out


def quantile(hist, q: float) -> float | None:
    """The value, in seconds, under which the share ``q`` of the entries
    lie, interpolated inside the bucket it falls in. None where the
    histogram holds nothing."""
    buckets = _filled(hist)
    if not buckets:
        return None
    rank = q * sum(b["count"] for _, b in buckets)
    seen, below = 0.0, 0.0
    for bound, b in buckets:
        if seen + b["count"] >= rank:
            if bound == float("inf"):
                return b["sum_s"] / b["count"]
            low = max(below, bound / RATIO)
            return low + (bound - low) * (rank - seen) / b["count"]
        seen, below = seen + b["count"], bound
    return buckets[-1][0]


def share_of(hist, share: float, part: str, whole: str) -> float | None:
    """``part`` as a percentage of ``whole`` over the slowest ``share``."""
    tail = slowest(hist, share)
    if tail is None or part not in tail or not tail.get(whole):
        return None
    return 100.0 * tail[part] / tail[whole]


def per_entry(hist, share: float, field: str) -> float | None:
    """``field`` an entry, over the slowest ``share``."""
    tail = slowest(hist, share)
    if tail is None or field not in tail:
        return None
    return tail[field] / tail["count"]

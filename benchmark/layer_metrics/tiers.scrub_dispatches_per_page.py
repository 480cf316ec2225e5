"""Device programs the tier store's scrubs took, a page it freed: the
program's ``frees`` counters over the window (``scrub_dispatches`` over
``pages``; pages freed together are scrubbed a dispatch a group, so under 1
where sessions end with many pages). A program without the counters, or a
window that freed nothing, has nothing to read."""


def read(stats, spans, trace, cell):
    frees = stats.get("frees")
    if not frees or not frees.get("pages"):
        return None
    return frees["scrub_dispatches"] / frees["pages"]

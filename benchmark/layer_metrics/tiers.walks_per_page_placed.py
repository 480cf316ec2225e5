"""Walks the tier store made over every live page, a page it placed: the
program's ``places`` counters over the window (``walks`` over ``pages``; the
store keeps a count a tier and walks only to find a victim, so 0 while every
page fits under HOT's high mark, and above 0 where pages are demoted). A
program without the counters, or a window that placed nothing, has nothing
to read."""


def read(stats, spans, trace, cell):
    places = stats.get("places")
    if not places or not places.get("pages"):
        return None
    return places["walks"] / places["pages"]

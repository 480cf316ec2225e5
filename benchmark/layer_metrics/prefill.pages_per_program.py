"""Whole pages of prompt a page program took: the program's ``prefill.pages``
counter over its ``batch.prefill_chunks`` (one a dispatch of the family's
page program) in the window. 1 for a family whose page program takes one
page; up to its ``chunk_pages`` for one that takes a chunk of several. A
program without the ``prefill.pages`` counter (a parent that lacks it), or a
window with no page program, reports nothing."""


def read(stats, spans, trace, cell):
    pages = (stats.get("prefill") or {}).get("pages")
    chunks = (stats.get("batch") or {}).get("prefill_chunks")
    if pages is None or not chunks:
        return None
    return pages / chunks

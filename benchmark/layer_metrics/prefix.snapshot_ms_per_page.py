"""Host wall time to take and store one carry snapshot (read the seat or
the session's own carry, pack it, put it in a slot of the store): the
``prefix.snapshot`` span's total over its count, one span a snapshot. A
program without the span (a family without a carry, a parent that lacks it)
reports nothing."""


def read(stats, spans, trace, cell):
    span = spans.get("prefix.snapshot")
    if not span or not span["count"]:
        return None
    return 1e3 * span["total_s"] / span["count"]

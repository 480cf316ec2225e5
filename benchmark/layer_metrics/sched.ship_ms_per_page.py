"""Host wall time to ship one finished page (slice the tail, pull it to the
host, store it, publish it, re-probe the prefix cache): the ``step.ship``
and ``prefill.ship`` spans over their count."""

OPS = ("step.ship", "prefill.ship")


def read(stats, spans, trace, cell):
    found = [spans[op] for op in OPS if op in spans]
    pages = sum(s["count"] for s in found)
    if not pages:
        return None
    return 1e3 * sum(s["total_s"] for s in found) / pages

"""Share of the ``tick`` spans' wall time that none of the tick's six child
spans covers: host time inside ``ServingEngine._tick`` that has no name.
Every span name has one parent, so the tick's self time is its total less
its children's totals."""

CHILDREN = ("tick.admit", "tick.match", "serve_prefill_chunk",
            "tick.select", "serve_batch_step", "tick.finish")


def read(stats, spans, trace, cell):
    tick = spans.get("tick")
    if not tick or not tick["total_s"]:
        return None
    named = sum(spans[op]["total_s"] for op in CHILDREN if op in spans)
    return 100.0 * (tick["total_s"] - named) / tick["total_s"]

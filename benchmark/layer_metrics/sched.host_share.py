"""Share of the tick's wall time in which the host is not waiting on the
device: the ``tick`` spans less ``step.sync`` and ``prefill.sync`` (the two
places the host blocks on a result), over the ``tick`` spans."""

SYNCS = ("step.sync", "prefill.sync")


def read(stats, spans, trace, cell):
    tick = spans.get("tick")
    if not tick or not tick["total_s"] or "step.sync" not in spans:
        return None
    waited = sum(spans[op]["total_s"] for op in SYNCS if op in spans)
    return 100.0 * (tick["total_s"] - waited) / tick["total_s"]

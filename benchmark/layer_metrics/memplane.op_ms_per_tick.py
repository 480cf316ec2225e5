"""Host wall time inside the memory plane's one-sided operations (``put``,
``get``, ``copy`` and their remote legs ``dcn_put``, ``dcn_get``: the
program's spans) per scheduler tick of the window. Prefetch workers' gets
run beside the tick and count here too."""

OPS = ("put", "get", "copy", "dcn_put", "dcn_get")


def read(stats, spans, trace, cell):
    ticks = cell["window"]["ticks"]
    if not ticks:
        return None
    total = sum(spans[op]["total_s"] for op in OPS if op in spans)
    return 1e3 * total / ticks

"""Mean host wall time of one whole scheduler tick: the program's ``tick``
spans over the window (admission, prefix matching, prefill chunks, seating,
the fused step and the finish loop; ``sched.tick_ms`` is the fused step
alone). Times the window's ticks it is the window, less the load
generator's own time between ticks."""


def read(stats, spans, trace, cell):
    s = spans.get("tick")
    if not s or not s["count"]:
        return None
    return 1e3 * s["total_s"] / s["count"]

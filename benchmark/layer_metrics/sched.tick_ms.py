"""Mean host wall time of one fused batch step, residency and the arg-max
sync included: the program's ``serve_batch_step`` spans over the window."""


def read(stats, spans, trace, cell):
    s = spans.get("serve_batch_step")
    if not s or not s["count"]:
        return None
    return 1e3 * s["total_s"] / s["count"]

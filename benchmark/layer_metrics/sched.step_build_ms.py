"""Host wall time spent building one fused step before it is dispatched:
residency, the page pool and the arguments (``step.residency`` +
``step.pool`` + ``step.args``) per ``serve_batch_step``."""

PARTS = ("step.residency", "step.pool", "step.args")


def read(stats, spans, trace, cell):
    step = spans.get("serve_batch_step")
    if not step or not step["count"] or not all(op in spans for op in PARTS):
        return None
    return 1e3 * sum(spans[op]["total_s"] for op in PARTS) / step["count"]

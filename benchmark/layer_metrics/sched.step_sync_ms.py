"""Host wall time from the fused step's dispatch to its arg-max on the host
(``step.dispatch`` + ``step.sync``) per ``serve_batch_step``. Read beside
``step.device_ms``: the difference is launch and transfer."""

PARTS = ("step.dispatch", "step.sync")


def read(stats, spans, trace, cell):
    step = spans.get("serve_batch_step")
    if not step or not step["count"] or not all(op in spans for op in PARTS):
        return None
    return 1e3 * sum(spans[op]["total_s"] for op in PARTS) / step["count"]

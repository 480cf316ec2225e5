"""Mean host wall time of one prefill chunk (prefix re-probe, residency,
the context's concatenate, the page program, the first token's sync when
the prompt ends, the ship): the program's ``serve_prefill_chunk`` spans
over the window. Read beside the page program's device time."""


def read(stats, spans, trace, cell):
    s = spans.get("serve_prefill_chunk")
    if not s or not s["count"]:
        return None
    return 1e3 * s["total_s"] / s["count"]

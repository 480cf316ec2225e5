"""Share of the time to first token spent after a prompt's whole pages were
done: the sub-page remainder riding the fused step one token a tick, waits
for a seat included. The program's TTFT parts (``ServingStats``) over the
sessions whose first token fell in the window: ``tail_s`` over
``queue_s + chunk_s + tail_s``."""


def read(stats, spans, trace, cell):
    parts = stats.get("ttft", {}).get("parts")
    if not parts:
        return None
    whole = parts["queue_s"] + parts["chunk_s"] + parts["tail_s"]
    if whole <= 0:
        return None
    return 100.0 * parts["tail_s"] / whole

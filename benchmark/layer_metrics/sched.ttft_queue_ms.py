"""Mean wait for a place among the ``max_active`` sessions, submit to
admission, per session whose first token fell in the window: the program's
TTFT part ``queue_s`` over ``ttft.count``."""


def read(stats, spans, trace, cell):
    ttft = stats.get("ttft", {})
    if not ttft.get("parts") or not ttft.get("count"):
        return None
    return 1e3 * ttft["parts"]["queue_s"] / ttft["count"]

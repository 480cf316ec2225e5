"""Milliseconds a session waited on a page that was not in HOT (a fault or
a prefetch that lost the race), per generated token: the program's
``stall_s`` over the window."""


def read(stats, spans, trace, cell):
    tokens = cell["window"]["tokens"]
    if not tokens:
        return None
    return 1e3 * stats["stall_s"] / tokens

"""MiB of pages moved between tiers (promotions and demotions, one page
each) per generated token, over the window."""


def read(stats, spans, trace, cell):
    tokens = cell["window"]["tokens"]
    if not tokens:
        return None
    moves = stats["moves"]["promote"] + stats["moves"]["demote"]
    return moves * cell["page_bytes"] / 2**20 / tokens

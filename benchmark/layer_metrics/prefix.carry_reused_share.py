"""Prompt tokens served from adopted prefix extents whose carry snapshot was
restored, as a share of the prompt tokens submitted, over the requests
submitted in the window (each result's ``prefix_tokens_reused``): what
``prefix.reused_share`` reads, held to the program's own count that every
adoption of the window set the session's carry from the extent it adopted
last (``prefix.adoptions`` == ``prefix.carry_restores``). Nothing to read
where the two disagree (a family without a carry restores none), where the
program has no such counters, or where the cell runs without a prefix
cache."""


def read(stats, spans, trace, cell):
    win = cell["window"]
    prefix = stats.get("prefix") or {}
    restores = prefix.get("carry_restores")
    if (not cell["traffic"]["engine"]["prefix_cache"]
            or not win["prompt_tokens"] or not restores
            or restores != prefix.get("adoptions")):
        return None
    return 100.0 * win["reused_tokens"] / win["prompt_tokens"]

"""Prompt tokens the prefix cache supplied, as a share of the prompt tokens
submitted, over the requests submitted in the window (each result's
``prefix_tokens_reused``). Nothing to read where the cell runs without a
prefix cache."""


def read(stats, spans, trace, cell):
    win = cell["window"]
    if not cell["traffic"]["engine"]["prefix_cache"] or not win["prompt_tokens"]:
        return None
    return 100.0 * win["reused_tokens"] / win["prompt_tokens"]

"""(layer, position) pairs the seated sessions' live pages held, as a share
of what they would have held had every cached layer kept every position,
summed over the fused steps: the program's ``kv.positions_held`` over
``kv.positions_whole``. 100 where no kind of page drops; under it where
the pages of a sliding-window kind are dropped as they leave the window
(lower is less memory and less for a step to read). A program without the
counters (a parent that lacks them), or a window with no fused step, has
nothing to read."""


def read(stats, spans, trace, cell):
    kv = stats.get("kv")
    if not kv or not kv["positions_whole"]:
        return None
    return 100.0 * kv["positions_held"] / kv["positions_whole"]

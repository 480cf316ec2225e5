"""Pages the tier store wrote into HOT a device program, where it wrote a
group at a time: the program's ``hot_writes`` counters over the window
(``pages`` over ``dispatches``; a window-family prefill chunk places its
pages a kind at a time, so near ``prefill.pages_per_program``). A program
without the counters (a parent that lacks them), or a window in which no
group was written (a family that ships one page at a time), reports
nothing."""


def read(stats, spans, trace, cell):
    writes = stats.get("hot_writes")
    if not writes or not writes.get("dispatches"):
        return None
    return writes["pages"] / writes["dispatches"]

"""Seats of the fused steps whose session's tail the engine's tail stack
already held, as a share of the seats stepped: the program's tails
counters over the window (``seats_kept`` cost no dispatch before the step;
``seats_written`` were written, moved or placed in a new stack). A program
without the counters has nothing to read."""


def read(stats, spans, trace, cell):
    tails = stats.get("tails")
    if not tails:
        return None
    seats = tails["seats_kept"] + tails["seats_written"]
    if not seats:
        return None
    return 100.0 * tails["seats_kept"] / seats

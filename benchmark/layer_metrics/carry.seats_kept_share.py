"""Seats of the fused steps whose session's recurrent carry the engine's
carry stack already held, as a share of the seats stepped: the program's
carry counters over the window (``seats_kept`` cost no dispatch before the
step; ``seats_written`` were written in for a joiner or moved with their
seat). A program without the counters, or a family without a carry (both
counters stay 0), has nothing to read."""


def read(stats, spans, trace, cell):
    carry = stats.get("carry")
    if not carry:
        return None
    seats = carry["seats_kept"] + carry["seats_written"]
    if not seats:
        return None
    return 100.0 * carry["seats_kept"] / seats

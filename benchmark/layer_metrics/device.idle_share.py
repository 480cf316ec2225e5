"""Share of the traced span in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced seconds)."""


def read(stats, spans, trace, cell):
    if trace is None or not cell["traced_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / cell["traced_s"])

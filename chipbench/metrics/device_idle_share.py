"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (%)."""


def read(trace, info):
    busy = trace.busy_s()
    if not busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace.window_s)

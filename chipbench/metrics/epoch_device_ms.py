"""Device busy time per epoch: the union of op intervals in the traced
window, averaged over chips, over the epochs in the window (ms)."""


def read(trace, info):
    busy = trace.busy_s()
    if not busy or not info["epochs"]:
        return None
    return 1e3 * sum(busy) / len(busy) / info["epochs"]

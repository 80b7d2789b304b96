"""Device busy time per Newton solve: the traced window's busy time per
chip over the solves that chip ran (ms)."""


def read(trace, info):
    solves = (info["epochs"] * info["generations_per_epoch"]
              * info["islands_per_chip"] * info["pop_per_island"]
              * info["solves_per_eval"])
    busy = trace.busy_s()
    if not solves or not busy:
        return None
    return 1e3 * sum(busy) / len(busy) / solves

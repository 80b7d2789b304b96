"""Device time of the lu factorizations and triangular solves per Newton
solve, averaged over chips (ms)."""
from chipbench.metrics._ops import lu, per_chip_time_s


def read(trace, info):
    solves = (info["epochs"] * info["generations_per_epoch"]
              * info["islands_per_chip"] * info["pop_per_island"]
              * info["solves_per_eval"])
    times = per_chip_time_s(trace, lu)
    if not solves or not any(times):
        return None
    return 1e3 * sum(times) / len(times) / solves

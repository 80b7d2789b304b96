"""Device time under the program scope ``chambga.migration`` per epoch,
averaged over chips (ms)."""
from chipbench.metrics._ops import per_unit_ms


def read(trace, info):
    return per_unit_ms(trace, "chambga.migration", info["epochs"])

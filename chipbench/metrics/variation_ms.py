"""Device time under the program scope ``chambga.variation`` per generation,
averaged over chips (ms)."""
from chipbench.metrics._ops import per_unit_ms


def read(trace, info):
    return per_unit_ms(trace, "chambga.variation",
                       info["epochs"] * info["generations_per_epoch"])

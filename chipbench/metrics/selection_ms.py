"""Device time under the program scope ``chambga.selection`` per generation,
averaged over chips (ms)."""
from chipbench.metrics._ops import per_unit_ms


def read(trace, info):
    return per_unit_ms(trace, "chambga.selection",
                       info["epochs"] * info["generations_per_epoch"])

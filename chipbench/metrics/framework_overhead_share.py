"""Share of the device's busy time spent outside the fitness: the self
time of the ops not under the program scope ``chambga.fitness`` over that
of all ops, averaged over chips (%). The framework's overhead, read on the
device: selection, variation, survivors, migration, dispatch and whatever
the program runs unscoped."""
from chipbench.metrics._ops import scoped, under


def read(trace, info):
    per_chip = scoped(trace)
    if per_chip is None:
        return None
    shares = [100.0 * sum(v for k, v in chip.items()
                          if not under(k, "chambga.fitness"))
              / sum(chip.values()) for chip in per_chip if chip]
    return sum(shares) / len(shares)

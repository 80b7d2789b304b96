"""What the per-layer readers share: which device ops belong to a layer."""
from __future__ import annotations


def lu(op) -> bool:
    """The LU factorization's custom calls and the triangular solves (on
    the TPU, custom calls that invert the diagonal blocks). An op's text
    names its own call target and opcode; operands appear only by name,
    so an op that reads an LU result does not match."""
    return ('custom_call_target="LuDecomposition' in op.name
            or 'custom_call_target="InvertDiagBlocks' in op.name
            or " triangular-solve(" in op.name)


def per_chip_time_s(trace, match) -> list:
    return [sum(o.dur for o in dev) * 1e-9 for dev in trace.ops(match)]


def under(scope: str, name: str) -> bool:
    """Whether an op of ``scope`` (``Op.scope``) lies under scope ``name``."""
    return name in scope.split("/")


def scoped(trace):
    """Per chip, the self time (s) per op scope; None where no op of the
    trace carries a scope (no map)."""
    per_chip = trace.scope_s()
    return per_chip if any(k for chip in per_chip for k in chip) else None


def per_unit_ms(trace, name: str, units: int):
    """Device time under scope ``name`` per unit of work, averaged over
    chips (ms); None where no op of the trace runs under it."""
    per_chip = scoped(trace)
    if not units or per_chip is None:
        return None
    times = [sum(v for k, v in chip.items() if under(k, name))
             for chip in per_chip]
    if not any(times):
        return None
    return 1e3 * sum(times) / len(times) / units

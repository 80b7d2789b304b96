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

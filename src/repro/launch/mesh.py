"""Production mesh factory.

Defined as a FUNCTION so importing this module never touches jax device
state. The dry-run entrypoint (dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; real deployments get the mesh from the TPU topology.

Single pod: v5e 16x16 (256 chips), axes (data, model).
Multi-pod:  2 pods = 512 chips, axes (pod, data, model) — `pod` is pure
data parallelism across the inter-pod links (optionally with compressed
gradient reduction, train/compress.py).

Every mesh built here has Auto axes: sharding comes from the GSPMD
partitioner and ``with_sharding_constraint`` (``jax.make_mesh`` alone
gives Explicit axes, which reject those constraints).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` over ``devices`` (default: the first
    prod(shape) local devices) with every axis Auto."""
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist locally (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))

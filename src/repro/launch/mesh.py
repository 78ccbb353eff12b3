"""Mesh construction: every mesh in the repo is built here.

``jax.make_mesh`` makes Explicit axes by default on current jax; the sharded
PPR steps slice their ``shard_map`` output back to ``V`` rows, which Explicit
axes refuse whenever ``V`` does not divide the shard count.  Auto axes let
the compiler place that slice, so every mesh here is built with them.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """A mesh of ``shape`` over ``axes`` (Auto axis types), on ``devices`` or
    the first ``prod(shape)`` visible devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def cpu_host_devices(n: int) -> None:
    """Expose ``n`` host devices when JAX runs on the CPU (``JAX_PLATFORMS=cpu``),
    so mesh paths run without a chip.  Sets nothing on any other platform, and
    keeps a device count already in ``XLA_FLAGS``; call it before JAX
    initializes its backend."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CI-scale distributed tests (requires ≥ data·model devices)."""
    return make_mesh((data, model), ("data", "model"))

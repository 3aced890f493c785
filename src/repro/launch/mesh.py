"""Production mesh definitions.

Functions, not module-level constants, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init; smoke
tests and benches must keep seeing 1 device).

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod : 2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is pure data parallelism over the inter-pod (DCI) links; "model" stays
inside the pod where ICI bandwidth lives.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo: `jax.make_mesh` with Auto axes. The repo's
    sharding is GSPMD-style (with_sharding_constraint, shard_map specs,
    NamedSharding placement); jax's default Explicit axes reject it."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, *, model: int = 1):
    """Small mesh over the actually-present devices (tests, examples)."""
    devs = jax.devices()
    n = n or len(devs)
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


# TPU v5e hardware constants used by the roofline (benchmarks read these)
PEAK_FLOPS_BF16 = 197e12          # per chip
PEAK_OPS_INT8 = 394e12            # per chip (MXU int8)
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per-chip usable)
VMEM_BYTES = 128 * 2 ** 20        # ~128 MiB VMEM per chip
HBM_BYTES = 16 * 2 ** 30          # 16 GiB HBM per chip

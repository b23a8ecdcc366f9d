"""Production meshes.

Target hardware: TPU v5e pods — 16x16 = 256 chips per pod; multi-pod runs
add a leading "pod" axis (2 pods = 512 chips for the dry-run; the axis
generalizes to any pod count). Defined as FUNCTIONS so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis in ``AxisType.Auto`` mode.

    The installed jax's ``jax.make_mesh`` defaults every axis to
    ``Explicit`` sharding, under which an ambiguous gather (the sharded embedding
    lookup, ``outs[i]`` after a ``shard_map``) raises ``ShardingTypeError``.
    This repo's partition rules are written for GSPMD propagation, so every
    mesh it builds goes through here.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return make_mesh(shape, axes, devices=devices)


def make_replay_mesh(n_devices: int | None = None,
                     axis: str = "data") -> jax.sharding.Mesh:
    """1-D mesh over the fused-replay batch dimension.

    ``axis`` defaults to ``"data"`` so ``partition.DEFAULT_RULES`` resolves
    the logical ``"batch"`` axis onto it. ``n_devices=None`` takes every
    local device — the ``REPRO_MESH=all`` configuration.
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"need a positive device count, got {n_devices!r}")
    if n > len(devices):
        raise RuntimeError(
            f"need {n} devices for the replay mesh, have {len(devices)} — "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "before any jax import")
    return make_mesh((n,), (axis,), devices=devices[:n])


def make_small_mesh(n_data: int = 2, n_model: int = 2) -> jax.sharding.Mesh:
    """CPU-test mesh (uses however many host devices exist)."""
    n = n_data * n_model
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return make_mesh((n_data, n_model), ("data", "model"), devices=devices)


# TPU v5e per-chip hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link (~4 links usable per chip)

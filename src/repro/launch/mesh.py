"""Mesh construction and the device peak table.

Functions, not module-level constants: importing this module never touches
jax device state.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing
jax so 512 placeholder CPU devices exist; smoke tests and the scan path
see the default single device.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo: Auto-typed axes, so sharding constraints
    inside jit take the logical-rule specs (``jax.make_mesh`` defaults
    to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices this host actually has."""
    return make_mesh((data, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Per-chip peak rates used by the roofline terms."""

    bf16_flops: float            # FLOP/s
    hbm_bw: float                # bytes/s
    ici_bw_per_link: float       # bytes/s per link, one direction
    ici_links_per_axis: int      # torus: links per mesh-axis direction
    dcn_bw: float                # bytes/s per chip, pod axis (multi-pod)


#: Published peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s ICI over four links.  The DCN rate is the multi-pod
#: model's assumption, not a published figure.
PEAKS: dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bw=819e9,
                               ici_bw_per_link=50e9, ici_links_per_axis=1,
                               dcn_bw=25e9),
}

#: The chip the production meshes and the modeled rooflines describe.
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of ``kind``; an unknown kind is an error, not a default."""
    if kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]

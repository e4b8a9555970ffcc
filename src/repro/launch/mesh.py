"""Mesh construction and the per-chip peaks of the TPU target.

``make_mesh`` and ``make_production_mesh`` are FUNCTIONS so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the first
jax call to obtain enough placeholder devices; the rest of the repo
(tests, benchmarks, examples) sees the 1 real CPU device.

Production axes (TPU v5e target):
  * single-pod: (16, 16) -> ("data", "model")       — 256 chips
  * multi-pod : (2, 16, 16) -> ("pod", "data", "model") — 512 chips

"data" carries the global batch and the FL-client dim; "model" carries
tensor/expert parallelism; "pod" is the DCN boundary — the top level of
the paper's aggregation hierarchy aligns with it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    Current JAX builds ``Explicit`` axes by default, and
    ``with_sharding_constraint`` (the ``models.sharding`` shard hints)
    refuses those; every mesh in this repo is an Auto mesh. ``devices``
    defaults to ``jax.devices()``.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Published per-chip peaks, keyed by ``jax.Device.device_kind`` — the
# roofline denominators. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect.
TARGET_DEVICE_KIND = "TPU v5 lite"   # the chip the production meshes model
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12,       # FLOP/s
                    "hbm_bw": 819e9,            # bytes/s
                    "ici_bw": 1600e9 / 8},      # bytes/s per chip
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unlisted kind raises."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                         ) from None


def mesh_chip_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n

"""Hardware model of the TPU chips the system targets.

Every hardware-aware decision in WPK (search fitness, constraint checking,
roofline analysis, backend selection) reads from this single module so that
re-targeting (e.g. v5p, Trainium) is a one-file change.

Numbers are the ones mandated for the roofline analysis:
  * 197 TFLOP/s bf16 per chip (MXU peak)
  * 819 GB/s HBM bandwidth per chip
  * ~50 GB/s per ICI link
plus micro-architectural facts needed by the kernel schedule templates:
  * VMEM is 128 MiB per v5e core.  A kernel's working set (every block the
    Pallas pipeline holds, double-buffered, plus its scratch) must fit in
    `vmem_budget_bytes`, which every `pallas_call` also hands the compiler
    as `vmem_limit_bytes`; the rest of VMEM is headroom for the compiler's
    own temporaries.
  * The MXU is a 128x128 systolic array; sublane tiling is (8, 128) for f32
    and (16, 128) for bf16 — block dims should be multiples of these.

`chip_for_kind` maps the `device_kind` a JAX device reports to its `Chip`;
an unknown kind is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Chip:
    """One accelerator chip."""

    name: str = "tpu_v5e"
    # Compute
    peak_bf16_flops: float = 197e12  # FLOP/s
    peak_f32_flops: float = 49.25e12  # MXU f32 is ~1/4 of bf16 on v5e-class
    # Memory
    hbm_bytes: int = 16 * 1024**3
    hbm_bw: float = 819e9  # B/s
    vmem_bytes: int = 128 * 1024**2
    # what one kernel may use: 3/4 of VMEM, the rest left to the compiler
    vmem_budget_bytes: int = 96 * 1024**2
    # Interconnect
    ici_link_bw: float = 50e9  # B/s per link per direction
    ici_links_per_axis: int = 1  # conservative: 1 usable link per mesh axis
    dcn_bw: float = 25e9  # B/s per host, pod-to-pod (data-centre network)
    # MXU / VPU geometry
    mxu_dim: int = 128
    lane: int = 128  # minor-most register dim
    sublane_f32: int = 8
    sublane_bf16: int = 16
    vpu_flops: float = 4e12  # elementwise throughput ceiling

    def sublane(self, dtype) -> int:
        itemsize = np.dtype(dtype).itemsize
        if itemsize >= 4:
            return self.sublane_f32
        if itemsize == 2:
            return self.sublane_bf16
        return 32  # int8/fp8

    def peak_flops(self, dtype) -> float:
        itemsize = np.dtype(dtype).itemsize
        if itemsize >= 4:
            return self.peak_f32_flops
        return self.peak_bf16_flops


TPU_V5E = Chip()

# Secondary target kept to demonstrate the hardware-aware search re-targets:
# same search code, different constants -> different best configs.
TPU_V5P = Chip(
    name="tpu_v5p",
    peak_bf16_flops=459e12,
    peak_f32_flops=114.75e12,
    hbm_bytes=95 * 1024**3,
    hbm_bw=2765e9,
    vmem_bytes=64 * 1024**2,
    vmem_budget_bytes=48 * 1024**2,
    ici_link_bw=100e9,
)

CHIPS = {"tpu_v5e": TPU_V5E, "tpu_v5p": TPU_V5P}

# `device_kind` as JAX reports it -> (chip, published source of its numbers).
# Peaks and HBM: Google Cloud TPU documentation, "TPU v5e" and "TPU v5p".
# VMEM per TensorCore: the Pallas TPU hardware table,
# `jax.experimental.pallas.tpu.get_tpu_info` (JAX 0.9.0).
DEVICE_KINDS = {
    "TPU v5 lite": (TPU_V5E, "cloud.google.com/tpu/docs/v5e; "
                             "pallas.tpu.get_tpu_info"),
    "TPU v5": (TPU_V5P, "cloud.google.com/tpu/docs/v5p; "
                        "pallas.tpu.get_tpu_info"),
}


def chip_for_kind(device_kind: str) -> Chip:
    """The `Chip` of a device by its `device_kind`; ValueError if unknown."""
    try:
        return DEVICE_KINDS[device_kind][0]
    except KeyError:
        raise ValueError(
            f"no hardware model for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_KINDS)}") from None


def align_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def mxu_padded_dims(m: int, n: int, k: int, chip: Chip, dtype) -> Tuple[int, int, int]:
    """Dims as the MXU actually sees them (padded to tile granularity)."""
    s = chip.sublane(dtype)
    return align_up(m, s), align_up(n, chip.lane), align_up(k, chip.lane)


def matmul_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def bytes_of(shape, dtype) -> int:
    return int(np.prod(shape)) * np.dtype(dtype).itemsize

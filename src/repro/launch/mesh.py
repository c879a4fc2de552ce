"""Production mesh construction and per-chip peak rates.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh helper for tests/examples (e.g. (8,) 'node' arrays)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peak rates, for roofline terms."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link, per direction
    source: str


# Keyed by ``jax.Device.device_kind``. A chip missing here is an error,
# never a default: a roofline against another chip's peaks is wrong.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s of interconnect over 4 links
        source="Google Cloud documentation, 'TPU v5e'",
    ),
}

# The chip the production meshes and the paper-scale rooflines model.
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peak rates of ``device_kind``; raises ``KeyError`` if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

"""Production meshes and per-chip peaks.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries pure
data parallelism (gradient all-reduce is the only DCN-crossing collective).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

import dataclasses

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`: the partitioner propagates
    shardings and `with_sharding_constraint` may name any axis (newer jax
    defaults to `Explicit` axes, which refuse both)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the local devices (tests, the four-chip smoke)."""
    return make_mesh((data, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # B/s
    ici_bw_per_link: float  # B/s


# Published per-chip peaks keyed by `jax.Device.device_kind`.  TPU v5e
# ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 819 GB/s of HBM bandwidth, 1,600 Gbit/s of inter-chip
# interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw_per_link=1600e9 / 8 / 4),
}

# the chip the dry-run and analytic rooflines project onto
V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a device missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None

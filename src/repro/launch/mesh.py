"""Mesh factories (production + serving) and per-chip peaks.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required by the dry-run contract.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over
# four links, i.e. 50 GB/s per link).  A kind missing here is an error,
# never a default: a roofline against the wrong chip is meaningless.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,   # FLOP/s per chip
        "hbm_bw": 819e9,        # bytes/s per chip
        "ici_bw": 50e9,         # bytes/s per link
        "hbm_bytes": 16e9,      # bytes per chip
    },
}


def device_peaks(kind: str) -> dict:
    """Peaks of one chip of ``kind`` (a ``jax.Device.device_kind``)."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; known kinds: "
            f"{sorted(DEVICE_PEAKS)}") from None


def _auto_axes(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_mesh(shape, axes):
    """General mesh for tests/examples (1x1 meshes exercise the same code)."""
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def make_serving_mesh(dp: int = 1, tp: int = 1):
    """Serving mesh: ("data", "model") of shape (dp, tp) over the devices
    present.  Raises when dp*tp exceeds ``jax.device_count()``: a run that
    asked for a mesh must never silently serve on one device.  Run tests
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to build
    real (2, 2) meshes on CPU."""
    if dp < 1 or tp < 1:
        raise ValueError(f"mesh axes must be positive, got dp={dp} tp={tp}")
    n = dp * tp
    avail = jax.device_count()
    if n > avail:
        raise ValueError(
            f"serving mesh dp x tp = {dp}x{tp} needs {n} devices but only "
            f"{avail} are available (on CPU, emulate with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return jax.make_mesh((dp, tp), ("data", "model"), **_auto_axes(2))

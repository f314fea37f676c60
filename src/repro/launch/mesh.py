"""Production meshes.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run (and only the
dry-run) forces 512 placeholder host devices before calling it.

Mesh shapes (TPU v5e-class pods):
  single-pod:  (16, 16)      axes ("data", "model")        = 256 chips
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the microbatch scan and `shard_hint` rely on the
    # compiler's sharding propagation, not on Explicit-axis typing
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))


# Published per-chip HBM bandwidth by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (16 GB HBM at 819 GB/s).
HBM_PEAK_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
}

# v5e-class hardware constants used by the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = HBM_PEAK_BYTES_PER_S["TPU v5 lite"]   # bytes/s
ICI_BW_PER_LINK = 50e9         # bytes/s/link
HBM_BYTES = 16 * (1 << 30)     # capacity


def hbm_peak_bytes_per_s(device_kind: str) -> float:
    """Published HBM bandwidth of one chip of ``device_kind``; a kind
    missing from :data:`HBM_PEAK_BYTES_PER_S` is an error, not a
    default."""
    try:
        return HBM_PEAK_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(f"no published HBM peak for device kind "
                       f"{device_kind!r}; known: "
                       f"{sorted(HBM_PEAK_BYTES_PER_S)}") from None

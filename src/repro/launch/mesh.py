"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
device initialization).

Production target: TPU v5e, 16x16 = 256 chips per pod; 2 pods = 512 chips
multi-pod. The "pod" axis carries only (hierarchically reduced) gradient
traffic; "data" is batch/FSDP; "model" is TP/EP/SP/vocab.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s/link (~4 links usable per chip)
ICI_LINKS = 4


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

"""Production meshes (TPU v5e target).

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod:  2×16×16 = 512 chips, axes (pod, data, model) — the `pod` axis is
data-parallel by default (per-pod FSDP + DCN gradient reduction) and can run
pipeline stages instead (repro.distributed.pipeline).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets the emulated device count before first use).
"""

from __future__ import annotations

import jax

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axes=("data",)):
    """Mesh over the first ``n`` locally visible (possibly emulated)
    devices — used by tests, examples and benchmarks."""
    n = n if n is not None else len(jax.devices())
    if len(axes) == 1:
        shape = (n,)
    else:
        shape = (n // 2, 2) if n % 2 == 0 else (n, 1)
    return compat.make_mesh(shape, axes)


# TPU v5e hardware constants (per chip) — roofline denominators.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link

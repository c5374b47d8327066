"""Production mesh builders.

Functions, not module-level constants — importing this module never
touches jax device state, so smoke tests keep seeing 1 device while the
dry-run (which sets XLA_FLAGS before any jax import) sees 512.

Topology (TPU v5e-class):
  single pod : (data=16, model=16)          = 256 chips
  multi-pod  : (pod=2, data=16, model=16)   = 512 chips
The "model" axis carries TP/EP collectives (fast intra-pod ICI rings);
"data" carries FSDP/DP; "pod" is the slow inter-pod hop — only the
once-per-step gradient reduction (optionally N:M-compressed) rides it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the train and serve
    steps are written for GSPMD propagation (sharding constraints, not
    typed ``out_sharding=`` on every gather), which ``Explicit`` axes —
    the default of ``jax.make_mesh`` since JAX 0.7 — would refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, model: int = 1, pods: int = 1):
    """Whatever this host actually has — smoke tests / examples / CI.

    pods > 1 adds the hierarchical "pod" axis (cross-pod gradient sync /
    compression paths) — real on a forced-device host
    (XLA_FLAGS=--xla_force_host_platform_device_count=N).
    """
    n = jax.device_count()
    if n % (model * pods):
        model = pods = 1
    if pods > 1:
        return _auto_mesh((pods, n // (model * pods), model),
                          ("pod", "data", "model"))
    return _auto_mesh((n // model, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return mesh.devices.size


# Hardware constants for the roofline terms (TPU v5e, per chip).
PEAK_FLOPS = 197e12      # bf16 FLOP/s
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s per link (~per-chip usable axis bandwidth)
VMEM_BYTES = 128 * 2**20
HBM_BYTES = 16 * 2**30

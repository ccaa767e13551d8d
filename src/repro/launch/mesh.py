"""Production mesh construction (TPU v5e pod targets).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips; the pod axis carries pure data/client parallelism —
in the federated mapping, clients live on (pod, data) and the only cross-pod
traffic is the per-round aggregation all-reduce + state-sync gather.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax

from ..sharding.rules import auto_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_axes(jax.make_mesh(shape, axes))


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return auto_axes(jax.make_mesh((n // model_parallel, model_parallel),
                                   ("data", "model")))


# TPU v5e hardware constants used by the roofline analysis.
TPU_V5E = {
    "peak_bf16_flops": 197e12,    # per chip
    "hbm_bw": 819e9,              # bytes/s per chip
    "ici_bw": 50e9,               # bytes/s per link
}

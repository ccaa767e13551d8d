"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §3):

* ``galore_adamw`` — the paper's optimizer step, fused project → moment
  update → precondition → project-back in one VMEM-resident pass.
* ``flash_attention`` — blockwise GQA attention, forward and custom-VJP
  backward (the training path's attention below ``attn_chunk``).
* ``rwkv6_scan`` — chunked WKV recurrence with VMEM-persistent state.
* ``lowrank_linear`` — lift-free factored weight read: one fused pass for
  ``scale·(x@W) + split-matmul rank-r delta`` (the federated client forward).
* ``batched_eigh`` — parallel-Jacobi eigensolver for the (B, r, r) SPD
  stacks of the batched 𝒮 path (r ≤ 64; LAPACK fallback on CPU).

``ops`` holds the jit'd public wrappers (interpret=True on CPU); ``ref``
holds the pure-jnp oracles the tests assert against.
"""
from . import ops, ref
from .ops import (batched_small_eigh, flash_attention, galore_adamw_step,
                  galore_precond_step, lowrank_linear, rwkv6_scan)

__all__ = ["ops", "ref", "batched_small_eigh", "flash_attention",
           "galore_adamw_step", "galore_precond_step", "lowrank_linear",
           "rwkv6_scan"]

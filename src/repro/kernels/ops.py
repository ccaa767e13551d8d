"""jit'd public wrappers for the Pallas kernels.

On the CPU the kernels run in ``interpret=True`` mode (the kernel body
executes as traced Python — correctness only); on a TPU backend they
compile to Mosaic. ``interpret`` is auto-detected from the backend.
:func:`use_kernels` is the one rule for when the model and optimizer paths
call a kernel instead of its XLA formulation.
"""
from __future__ import annotations

import jax

import jax.numpy as jnp

from .batched_eigh import MAX_JACOBI_DIM
from .batched_eigh import jacobi_eigh as _jacobi_eigh
from .flash_attention import flash_attention as _flash
from .galore_adamw import galore_adamw_step as _galore
from .galore_adamw import galore_precond_step as _galore_precond
from .lowrank_linear import lowrank_linear as _lowrank
from .lowrank_linear import lowrank_linear_batched as _lowrank_batched
from .rwkv6_scan import rwkv6_scan as _rwkv6


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_kernels() -> bool:
    """True on a TPU backend, unless the trace runs under an ambient mesh
    (``jax.set_mesh``) of more than one device. The TPU compiler cannot
    partition a Pallas call ("Mosaic kernels cannot be automatically
    partitioned"), so a program that XLA partitions over several devices
    keeps the XLA formulations of these ops; a one-device program, meshed
    or not, uses the kernels."""
    if jax.default_backend() != "tpu":
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or mesh.size == 1


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=512, block_k=256):
    return _flash(q, k, v, causal=causal, window=window, scale=scale,
                  block_q=block_q, block_k=block_k, interpret=_interpret())


def galore_adamw_step(w, g, basis, m, v, count, **kw):
    kw.setdefault("interpret", _interpret())
    return _galore(w, g, basis, m, v, count, **kw)


def galore_precond_step(g, basis, m, v, count, **kw):
    kw.setdefault("interpret", _interpret())
    return _galore_precond(g, basis, m, v, count, **kw)


def lowrank_linear(x, w, basis, rt, scale, **kw):
    kw.setdefault("interpret", _interpret())
    return _lowrank(x, w, basis, rt, scale, **kw)


def lowrank_linear_batched(x, w, bases, rts, scales, ids, **kw):
    kw.setdefault("interpret", _interpret())
    return _lowrank_batched(x, w, bases, rts, scales, ids, **kw)


def rwkv6_scan(r, k, v, w, u, s0=None, *, chunk=128):
    return _rwkv6(r, k, v, w, u, s0, chunk=chunk, interpret=_interpret())


def batched_small_eigh(a, *, mask=None, force=None, sweeps=12, block_b=8):
    """Eigendecomposition of a batched symmetric stack ``(..., n, n)``.

    Returns ``(lam, vec)`` ascending, matching ``jnp.linalg.eigh``. Routing:
    on TPU with n ≤ 64 the batched parallel-Jacobi Pallas kernel keeps the
    whole stack VMEM-resident (XLA's QDWH ``eigh`` is built for one large
    matrix, not (B, r, r) stacks); on CPU LAPACK's per-matrix ``syevd`` is
    already optimal, so the jnp path is the default — bit-identical to the
    pre-kernel behavior; :func:`use_kernels` also keeps a multi-device
    partitioned program on ``jnp``. ``force`` pins a path for parity tests:
    ``"jacobi"`` (interpret-mode on CPU) or ``"lapack"``.

    ``mask`` (bool, shaped like the batch dims ``a.shape[:-2]``) is the
    quarantine/participation bucket path: masked entries are solved as the
    identity (their payload never reaches the solver — both Jacobi rotations
    and LAPACK propagate a single NaN across the whole slice) and their
    eigenvalues are returned as exact zeros, so rank-revealing floors
    downstream drop the directions. The select is elementwise, so an
    all-true mask is bitwise identical to ``mask=None``.
    """
    n = a.shape[-1]
    if mask is not None:
        sel = jnp.asarray(mask, bool)[..., None, None]
        a = jnp.where(sel, a, jnp.eye(n, dtype=a.dtype))
    use_jacobi = (force == "jacobi" or
                  (force is None and use_kernels() and n <= MAX_JACOBI_DIM))
    if force == "lapack":
        use_jacobi = False
    if use_jacobi:
        lam, vec = _jacobi_eigh(a, sweeps=sweeps, block_b=block_b,
                                interpret=_interpret())
    else:
        lam, vec = jnp.linalg.eigh(a)
    if mask is not None:
        lam = jnp.where(jnp.asarray(mask, bool)[..., None], lam,
                        jnp.zeros((), lam.dtype))
    return lam, vec

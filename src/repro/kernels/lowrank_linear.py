"""Fused lift-free low-rank linear apply — the factored client weight read.

A factored client's effective weight is ``W_eff = scale·W + lift(R̃, B)``
(rank-r delta ``R̃`` around the broadcast base ``W``). Materializing
``W_eff`` costs an O(m·n·r) lift GEMM plus an O(m·n) transient buffer per
target leaf per local step. This kernel computes the *apply* instead,

  right-projected block (m ≥ n; basis B (n, r), delta R̃ (m, r)):
      y = scale·(x @ W) + (x @ R̃) @ Bᵀ
  left-projected block (m < n; basis B (m, r), delta R̃ (r, n)):
      y = scale·(x @ W) + (x @ B) @ R̃

as split matmuls — O(t·r·(m+n)) extra work on top of the unavoidable base
GEMM, with the dense ``m×n`` lifted weight never existing. One VMEM-resident
pass per row tile of ``x``: the base GEMM, both split GEMMs, and the scaled
add all happen before the tile's output leaves VMEM.

Grid handling mirrors ``galore_adamw.py``: the tile count is
``ceil(t / block)`` (``pl.cdiv``) with the trailing partial tile masked by
Pallas block clipping — no divisibility requirement on the token dim.

The kernel is the *forward* of the lift-free delta read; its backward (the
projected-cotangent VJP — grad wrt R̃ arrives already in rank-r coordinates)
lives in ``models.layers.lowrank_apply``, which consumes this kernel via
``ops.lowrank_linear`` on TPU.

``lowrank_linear_batched`` is the *serving* variant of the same apply: one
decode batch where every row carries its own adapter — the S-LoRA/Punica
shape. The base GEMM is shared across the batch; each grid program gathers
its row's ``(basis_g, R̃_g)`` blocks by the scalar-prefetched ``(B,)``
adapter-id operand (the id indexes the BlockSpec ``index_map``, so only the
selected adapter's factors are ever DMA'd — the ``(G, ·, r)`` tables stay
put no matter how many fine-tunes are resident); the ``(G,)`` scales are
gathered the same way, as a ``(G, 1, 1)`` array. Ragged
per-adapter ranks are handled upstream by zero-padding factors to the
table's r_max: zero basis/R̃ columns contribute exactly zero delta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mxu

RIGHT = "right"
LEFT = "left"


def infer_side(w_shape, basis_shape, rt_shape) -> str:
    """Recover the projection side from buffer shapes (Appendix A.1 layout:
    right ⇒ basis (n, r), delta (m, r); left ⇒ basis (m, r), delta (r, n))."""
    mm, nn = w_shape[-2:]
    dim, r = basis_shape[-2:]
    if dim == nn and rt_shape[-2:] == (mm, r):
        return RIGHT
    if dim == mm and rt_shape[-2:] == (r, nn):
        return LEFT
    raise ValueError(f"inconsistent lowrank shapes: w {w_shape}, "
                     f"basis {basis_shape}, rt {rt_shape}")


def _apply(x, w, basis, rt, scale, side):
    """``scale·(x @ w) + split-matmul(x, basis, rt)`` on one row tile. The
    base GEMM reads its operands in their stored dtype (bf16 products are
    exact in the fp32 accumulator); the rank-r factors are fp32."""
    base = mxu.dot(x, w)
    basis = basis.astype(jnp.float32)
    rt = rt.astype(jnp.float32)
    if side == RIGHT:
        # (bt, m) @ (m, r) @ (r, n)
        delta = mxu.dot(mxu.dot(x, rt), basis.T)
    else:
        # (bt, m) @ (m, r) @ (r, n)
        delta = mxu.dot(mxu.dot(x, basis), rt)
    return scale * base + delta


def _kernel(scale_ref, x_ref, w_ref, basis_ref, rt_ref, y_out, *, side):
    y = _apply(x_ref[...], w_ref[...], basis_ref[...], rt_ref[...],
               scale_ref[0, 0], side)
    y_out[...] = y.astype(y_out.dtype)


@functools.partial(jax.jit, static_argnames=("side", "block_rows",
                                             "interpret"))
def lowrank_linear(x, w, basis, rt, scale, *, side=None, block_rows=128,
                   interpret=False):
    """Fused ``y = scale·(x @ w) + split-matmul(x, basis, rt)`` for one block.

    x (..., t, m); w (m, n); right side: basis (n, r), rt (m, r); left side:
    basis (m, r), rt (r, n). ``scale`` is the scalar base multiplier
    (``base_scale = (1-ηλ)^t``). Returns y (..., t, n) in the base-GEMM
    result dtype; fp32 accumulation throughout.
    """
    side = side or infer_side(w.shape, basis.shape, rt.shape)
    lead = x.shape[:-1]
    mm, nn = w.shape
    x2 = x.reshape((-1, mm))
    t = x2.shape[0]
    bt = min(block_rows, t)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    r = basis.shape[-1]
    bshape = (nn, r) if side == RIGHT else (mm, r)
    rshape = (mm, r) if side == RIGHT else (r, nn)
    y = pl.pallas_call(
        functools.partial(_kernel, side=side),
        grid=(pl.cdiv(t, bt),),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),   # scale (SMEM-like)
                  pl.BlockSpec((bt, mm), lambda i: (i, 0)),
                  pl.BlockSpec((mm, nn), lambda i: (0, 0)),
                  pl.BlockSpec(bshape, lambda i: (0, 0)),
                  pl.BlockSpec(rshape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, nn), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nn), out_dtype),
        interpret=interpret,
    )(jnp.full((1, 1), scale, jnp.float32), x2, w, basis, rt)
    return y.reshape(lead + (nn,))


# ------------------------------------------- batched heterogeneous adapters --

def _batched_kernel(ids_ref, x_ref, w_ref, basis_ref, rt_ref, scale_ref,
                    y_out, *, side):
    """One grid program = one sequence's row tile. The adapter-dependent
    operands (basis/rt/scale) arrive already gathered: their BlockSpec
    index_maps consumed the scalar-prefetched ids, so block 0 here IS
    adapter ``ids[b]``'s block."""
    del ids_ref
    y = _apply(x_ref[0], w_ref[...], basis_ref[0], rt_ref[0],
               scale_ref[0, 0, 0], side)
    y_out[0] = y.astype(y_out.dtype)


@functools.partial(jax.jit, static_argnames=("side", "block_t", "interpret"))
def lowrank_linear_batched(x, w, bases, rts, scales, ids, *, side=None,
                           block_t=128, interpret=False):
    """Per-row heterogeneous-adapter apply for one shared base block.

    x (B, t, m) or (B, m); w (m, n) shared base; bases (G, n, r) right /
    (G, m, r) left; rts (G, m, r) right / (G, r, n) left; scales (G,)
    per-adapter base multipliers; ids (B,) int32 adapter index per row.
    Returns ``y[b] = scales[ids[b]]·(x[b] @ w) + split-matmul(x[b],
    bases[ids[b]], rts[ids[b]])`` — one compiled program regardless of G,
    duplicate ids welcome. The token dim tiles by ``block_t`` (ceil-div
    grid, trailing partial tile masked by Pallas block clipping).
    """
    squeeze_t = x.ndim == 2
    if squeeze_t:
        x = x[:, None, :]
    b, t, mm = x.shape
    nn = w.shape[-1]
    side = side or infer_side(w.shape, bases.shape[1:], rts.shape[1:])
    r = bases.shape[-1]
    bshape = (1, nn, r) if side == RIGHT else (1, mm, r)
    rshape = (1, mm, r) if side == RIGHT else (1, r, nn)
    bt = min(block_t, t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, pl.cdiv(t, bt)),
        in_specs=[
            pl.BlockSpec((1, bt, mm), lambda i, j, ids: (i, j, 0)),
            pl.BlockSpec((mm, nn), lambda i, j, ids: (0, 0)),
            pl.BlockSpec(bshape, lambda i, j, ids: (ids[i], 0, 0)),
            pl.BlockSpec(rshape, lambda i, j, ids: (ids[i], 0, 0)),
            # (G, 1, 1): a rank-3 block whose last two dims span the array,
            # the layout the TPU compiler accepts for a per-adapter scalar
            pl.BlockSpec((1, 1, 1), lambda i, j, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, nn), lambda i, j, ids: (i, j, 0)),
    )
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    y = pl.pallas_call(
        functools.partial(_batched_kernel, side=side),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, nn), out_dtype),
        interpret=interpret,
    )(jnp.asarray(ids, jnp.int32), x, w, bases, rts,
      jnp.asarray(scales, jnp.float32).reshape(-1, 1, 1))
    return y[:, 0, :] if squeeze_t else y

"""Fused GaLoreAdamW Pallas TPU kernels.

On GPU, GaLore is three GEMMs + elementwise ops with HBM round-trips between
them (project -> Adam update -> project-back -> weight update). These kernels
fuse the whole optimizer step for one weight block into a single VMEM-
resident pass, tiled over the block's long axis:

  right-projected block (basis B (N, r), moments (M, r)), per row-tile (bm, N):
    g̃  = g_i @ B            (MXU;  B stays resident across the grid)
    m̃  = β₁ m̃ + (1-β₁) g̃     (VPU)
    ṽ  = β₂ ṽ + (1-β₂) g̃²    (VPU)
    ũ  = m̂ / (√v̂ + ε)        (VPU, bias-corrected)
    u  = ũ @ Bᵀ              (MXU)
    w_i ← w_i − η u − η λ w_i

  left-projected block (basis B (M, r), moments (r, N)) is the transpose
  problem: the grid tiles *columns* (M, bn) and the two GEMMs become
  g̃ = Bᵀ g_j and u = B ũ, with B resident.

HBM traffic: read w, g once; write w once; m̃/ṽ are O(long_dim·r) — the dense
(M, N) gradient never round-trips between optimizer stages.

Grid handling: the tile count is ``ceil(dim / block)`` (``pl.cdiv``) — the
trailing partial tile is masked by Pallas block clipping (out-of-range reads
are padded, out-of-range writes dropped; every output element depends only on
its own row/column tile, so padding never contaminates valid lanes). There is
no divisibility requirement on M or N.

Two entry points:

* :func:`galore_adamw_step` — the full fused step ``(w, m, v) -> (w', m', v')``
  including the ambient AdamW weight update (lr + decoupled weight decay).
* :func:`galore_precond_step` — the preconditioning-only variant
  ``(g, m, v) -> (u, m', v')`` returning the ambient update direction; this is
  what ``core.galore.scale_by_galore`` wires into its chained-transformation
  hot path (weight decay / lr are applied by the rest of the chain).

Both accept stacked 3-D blocks ``(nb, M, N)`` (per-layer bases/moments with a
leading layer dim) by vmapping the 2-D kernel — under ``jax.vmap`` the batch
dim becomes an extra grid dimension, not a Python loop.
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


def _adam_update(gt, m_ref, v_ref, corr_ref, b1, b2, eps):
    """Shared Adam moment update + bias-corrected direction. ``corr_ref``
    holds the two bias corrections (1-β₁ᵗ, 1-β₂ᵗ), computed outside the
    kernel (ones when bias correction is off)."""
    m = b1 * m_ref[...] + (1.0 - b1) * gt
    v = b2 * v_ref[...] + (1.0 - b2) * gt * gt
    ut = (m / corr_ref[0]) / (jnp.sqrt(v / corr_ref[1]) + eps)
    return m, v, ut


def _project(g, basis, side):
    if side == RIGHT:
        return mxu.dot(g, basis)
    return mxu.dot(basis.T, g)


def _project_back(ut, basis, side):
    if side == RIGHT:
        return mxu.dot(ut, basis.T)
    return mxu.dot(basis, ut)


def _step_kernel(corr_ref, w_ref, g_ref, basis_ref, m_ref, v_ref,
                 w_out, m_out, v_out, *, side, b1, b2, eps, lr, weight_decay):
    g = g_ref[...].astype(jnp.float32)
    basis = basis_ref[...].astype(jnp.float32)
    gt = _project(g, basis, side)
    m, v, ut = _adam_update(gt, m_ref, v_ref, corr_ref, b1, b2, eps)
    u = _project_back(ut, basis, side)
    w = w_ref[...].astype(jnp.float32)
    w_out[...] = (w - lr * u - lr * weight_decay * w).astype(w_out.dtype)
    m_out[...] = m
    v_out[...] = v


def _precond_kernel(corr_ref, g_ref, basis_ref, m_ref, v_ref,
                    u_out, m_out, v_out, *, side, b1, b2, eps,
                    project_back=True):
    g = g_ref[...].astype(jnp.float32)
    basis = basis_ref[...].astype(jnp.float32)
    gt = _project(g, basis, side)
    m, v, ut = _adam_update(gt, m_ref, v_ref, corr_ref, b1, b2, eps)
    u_out[...] = _project_back(ut, basis, side) if project_back else ut
    m_out[...] = m
    v_out[...] = v


def infer_side(w_shape, basis_shape, m_shape) -> str:
    """Recover the projection side from buffer shapes (Appendix A.1 layout:
    right ⇒ basis (N, r), moments (M, r); left ⇒ basis (M, r), moments (r, N)).
    Square blocks with r == M are genuinely ambiguous and default to right —
    the ``proj_type=std`` convention."""
    mm, nn = w_shape[-2:]
    dim, r = basis_shape[-2:]
    if dim == nn and m_shape[-2:] == (mm, r):
        return RIGHT
    if dim == mm and m_shape[-2:] == (r, nn):
        return LEFT
    raise ValueError(f"inconsistent galore shapes: w {w_shape}, "
                     f"basis {basis_shape}, m {m_shape}")


def _block_specs(side, mm, nn, r, block):
    """Grid + BlockSpecs for one 2-D block. ``block`` tiles rows (right) or
    columns (left); the grid is ceil-div so non-divisible dims get a masked
    tail tile instead of an assertion."""
    if side == RIGHT:
        bm = min(block, mm)
        grid = (pl.cdiv(mm, bm),)
        wg = pl.BlockSpec((bm, nn), lambda i: (i, 0))
        basis = pl.BlockSpec((nn, r), lambda i: (0, 0))
        mv = pl.BlockSpec((bm, r), lambda i: (i, 0))
    else:
        bn = min(block, nn)
        grid = (pl.cdiv(nn, bn),)
        wg = pl.BlockSpec((mm, bn), lambda j: (0, j))
        basis = pl.BlockSpec((mm, r), lambda j: (0, 0))
        mv = pl.BlockSpec((r, bn), lambda j: (0, j))
    return grid, wg, basis, mv


def _bias_corrections(count, b1, b2, bias_correction):
    """(1-β₁ᵗ, 1-β₂ᵗ) as a (2,) fp32 operand, the arithmetic of
    ``core.galore._projected_adam``. Computed here because the TPU kernel
    compiler has no ``pow`` on a traced exponent."""
    if not bias_correction:
        return jnp.ones((2,), jnp.float32)
    c = jnp.asarray(count, jnp.float32)
    return jnp.stack([1 - b1 ** c, 1 - b2 ** c])


_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (2,) array in SMEM


@functools.partial(jax.jit, static_argnames=("side", "b1", "b2", "eps", "lr",
                                             "weight_decay", "block_rows",
                                             "interpret", "bias_correction"))
def galore_adamw_step(w, g, basis, m, v, count, *, side=None, b1=0.9, b2=0.999,
                      eps=1e-8, lr=1e-3, weight_decay=0.0,
                      block_rows=128, interpret=False, bias_correction=True):
    """One fused GaLoreAdamW step for a projected block.

    Right side: w, g (M, N); basis (N, r); m, v (M, r) fp32.
    Left side:  w, g (M, N); basis (M, r); m, v (r, N) fp32.
    Stacked 3-D blocks carry a leading layer dim on every buffer.
    count = post-increment step (bias correction). Returns (w', m', v').
    """
    side = side or infer_side(w.shape, basis.shape, m.shape)
    if w.ndim > 2:
        fn = functools.partial(galore_adamw_step, side=side, b1=b1, b2=b2,
                               eps=eps, lr=lr, weight_decay=weight_decay,
                               block_rows=block_rows, interpret=interpret,
                               bias_correction=bias_correction)
        return jax.vmap(lambda ww, gg, bb, mm_, vv: fn(ww, gg, bb, mm_, vv,
                                                       count))(w, g, basis, m, v)

    mm, nn = w.shape
    r = basis.shape[-1]
    grid, wg_spec, basis_spec, mv_spec = _block_specs(side, mm, nn, r,
                                                      block_rows)
    kernel = functools.partial(_step_kernel, side=side, b1=b1, b2=b2, eps=eps,
                               lr=lr, weight_decay=weight_decay)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_SCALARS, wg_spec, wg_spec, basis_spec, mv_spec, mv_spec],
        out_specs=[wg_spec, mv_spec, mv_spec],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret=interpret,
    )(_bias_corrections(count, b1, b2, bias_correction), w, g, basis, m, v)


@functools.partial(jax.jit, static_argnames=("side", "b1", "b2", "eps",
                                             "block_rows", "interpret",
                                             "bias_correction",
                                             "project_back"))
def galore_precond_step(g, basis, m, v, count, *, side=None, b1=0.9, b2=0.999,
                        eps=1e-8, block_rows=128, interpret=False,
                        bias_correction=True, project_back=True):
    """Fused project → Adam → project-back, returning the ambient update
    direction u (fp32) instead of applying it — the ``scale_by_galore`` hot
    path (lr / weight decay live elsewhere in the optimizer chain).

    Shapes as :func:`galore_adamw_step`; returns (u (M, N) fp32, m', v').
    ``project_back=False`` skips the final lift GEMM and returns the
    *projected* ũ in the moment shape ((M, r) right / (r, N) left) — the
    factored-delta client path, whose rank-r accumulator consumes ũ directly
    and never round-trips the dense (M, N) update through HBM.
    """
    side = side or infer_side(g.shape, basis.shape, m.shape)
    if g.ndim > 2:
        fn = functools.partial(galore_precond_step, side=side, b1=b1, b2=b2,
                               eps=eps, block_rows=block_rows,
                               interpret=interpret,
                               bias_correction=bias_correction,
                               project_back=project_back)
        return jax.vmap(lambda gg, bb, mm_, vv: fn(gg, bb, mm_, vv,
                                                   count))(g, basis, m, v)

    mm, nn = g.shape[-2:]
    r = basis.shape[-1]
    grid, wg_spec, basis_spec, mv_spec = _block_specs(side, mm, nn, r,
                                                      block_rows)
    kernel = functools.partial(_precond_kernel, side=side, b1=b1, b2=b2,
                               eps=eps, project_back=project_back)
    u_spec = wg_spec if project_back else mv_spec
    u_shape = g.shape if project_back else m.shape
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_SCALARS, wg_spec, basis_spec, mv_spec, mv_spec],
        out_specs=[u_spec, mv_spec, mv_spec],
        out_shape=[jax.ShapeDtypeStruct(u_shape, jnp.float32),
                   jax.ShapeDtypeStruct(m.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        interpret=interpret,
    )(_bias_corrections(count, b1, b2, bias_correction), g, basis, m, v)

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

Bf16 activations (the round's case) take the split-word path. The factors
are fp32 and ``x`` is bf16; an fp32 dot would run as a multi-pass bf16
emulation (``Precision.HIGHEST``) on r-wide operands padded to the MXU's
128, which costs more than the base GEMM it corrects. Instead every fp32
factor is split into three bf16 words, ``a = hi + mid + lo`` exactly
(3 × 8 significand bits = fp32's 24), and the words are packed into one
bf16 dot with fp32 accumulation:

  ``u = x @ F₁``: ``x`` is exact in one bf16 word, so ``x @ [F₁ʰⁱ|F₁ᵐⁱᵈ|F₁ˡᵒ]``
      is one (bt, m) × (m, 3r) pass whose three r-wide blocks, summed in
      fp32, are ``x @ F₁`` to fp32 rounding.
  ``u @ F₂``: both sides fp32; ``u`` is split per tile, and the nine
      (word of u, word of F₂) cross products are lined up along K —
      ``[uʰⁱ uʰⁱ uʰⁱ uᵐⁱᵈ … uˡᵒ] @ [F₂ʰⁱ; F₂ᵐⁱᵈ; F₂ˡᵒ; F₂ʰⁱ; …]`` — one pass
      of K = 9r ≤ 128 for r ≤ 14 (above, groups of ⌊128/r⌋ pairs, one
      pass each); at least as exact as ``HIGHEST``'s six products.

(F₁, F₂) is (R̃, Bᵀ) on the right side and (B, R̃) on the left. The factor
blocks have a constant ``index_map``, so they stay in VMEM across a
client's row tiles: they are split (and Bᵀ transposed) once, into VMEM
scratch, at each client's first row tile (the row-tile axis runs in order,
``arbitrary``; a vmapped client axis is a grid axis of its own). Each
product of bf16 words is exact in fp32, so the result differs from the
fp32 reference by fp32 accumulation rounding only. Fp32 activations keep
the fp32 ``HIGHEST`` dots (``_apply``): splitting the factors alone would
not make an fp32 ``x`` exact. The dtype of ``x`` is the only switch.

Grid handling mirrors ``galore_adamw.py``: the tile count is
``ceil(t / block)`` (``pl.cdiv``) with the trailing partial tile masked by
Pallas block clipping — no divisibility requirement on the token dim.

The kernel is the *forward* of the lift-free delta read; its backward (the
projected-cotangent VJP — grad wrt R̃ arrives already in rank-r coordinates)
lives in ``models.layers.lowrank_apply``, which consumes this kernel via
``ops.lowrank_linear`` on TPU.

``lowrank_linear_batched`` is the *serving* variant of the same apply (it
keeps the fp32 dots of ``_apply``: each grid program gathers another
adapter, so a once-per-client split does not carry over): one
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


def _split3(a):
    """Three bf16 words whose fp32 sum is ``a`` (fp32) exactly."""
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _pair_chunks(r):
    """The nine (word of u, word of F₂) cross products of two 3-word splits,
    in groups whose packed depth (pairs × r) is at most the MXU's 128 (one
    pair per group once r > 64); one group for r ≤ 14."""
    pairs = [(i, j) for i in range(3) for j in range(3)]
    per = max(1, 128 // r)
    return [pairs[i:i + per] for i in range(0, len(pairs), per)]


def _packed_kernel(scale_ref, x_ref, w_ref, basis_ref, rt_ref, y_out, f1_ref,
                   *f2_refs, side):
    """Bf16-activation body: ``scale·(x @ w) + (x @ F₁) @ F₂`` with both
    rank-r products as single bf16 MXU passes over split words (module
    docstring). ``f1_ref`` (3r, m) holds F₁ᵀ's words stacked; each
    ``f2_refs`` entry holds one pair group's F₂ words stacked along K. The
    rank-r intermediate is kept transposed, (r, bt): its three word blocks
    and its split are then whole sublane groups, not lane shuffles."""
    r = f1_ref.shape[0] // 3
    chunks = _pair_chunks(r)
    nt, tn = (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))

    @pl.when(pl.program_id(0) == 0)
    def _split_factors():
        basis = basis_ref[...].astype(jnp.float32)
        rt = rt_ref[...].astype(jnp.float32)
        f1, f2 = (rt, basis.T) if side == RIGHT else (basis, rt)
        f1_ref[...] = jnp.concatenate(_split3(f1.T), axis=0)
        # stacked as fp32 (exact) and cast once: r-row bf16 blocks would
        # not fall on bf16's 16-row sublane tiles
        words = [a.astype(jnp.float32) for a in _split3(f2)]
        for ref, chunk in zip(f2_refs, chunks):
            ref[...] = jnp.concatenate([words[j] for _, j in chunk],
                                       axis=0).astype(jnp.bfloat16)

    x = x_ref[...]
    base = mxu.dot(x, w_ref[...])
    u3 = mxu.dot(f1_ref[...], x, nt)                         # (3r, bt)
    u = u3[:r] + u3[r:2 * r] + u3[2 * r:]
    words = [a.astype(jnp.float32) for a in _split3(u)]
    delta = functools.reduce(jnp.add, [
        mxu.dot(jnp.concatenate([words[i] for i, _ in chunk],
                                axis=0).astype(jnp.bfloat16), ref[...], tn)
        for ref, chunk in zip(f2_refs, chunks)])
    y_out[...] = (scale_ref[0, 0] * base + delta).astype(y_out.dtype)


@functools.partial(jax.jit, static_argnames=("side", "block_rows",
                                             "interpret"))
def lowrank_linear(x, w, basis, rt, scale, *, side=None, block_rows=128,
                   interpret=False):
    """Fused ``y = scale·(x @ w) + split-matmul(x, basis, rt)`` for one block.

    x (..., t, m); w (m, n); right side: basis (n, r), rt (m, r); left side:
    basis (m, r), rt (r, n). ``scale`` is the scalar base multiplier
    (``base_scale = (1-ηλ)^t``). Returns y (..., t, n) in the base-GEMM
    result dtype; fp32 accumulation throughout. Bf16 ``x`` takes the
    split-word body, any other dtype the fp32 dots (module docstring).
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
    if x.dtype == jnp.bfloat16:
        kernel = _packed_kernel
        scratch = [pltpu.VMEM((3 * r, mm), jnp.bfloat16)] + [
            pltpu.VMEM((len(chunk) * r, nn), jnp.bfloat16)
            for chunk in _pair_chunks(r)]
    else:
        kernel, scratch = _kernel, []
    y = pl.pallas_call(
        functools.partial(kernel, side=side),
        grid=(pl.cdiv(t, bt),),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),   # scale (SMEM-like)
                  pl.BlockSpec((bt, mm), lambda i: (i, 0)),
                  pl.BlockSpec((mm, nn), lambda i: (0, 0)),
                  pl.BlockSpec(bshape, lambda i: (0, 0)),
                  pl.BlockSpec(rshape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bt, nn), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nn), out_dtype),
        scratch_shapes=scratch,
        # the split factors are written at row tile 0: tiles run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
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

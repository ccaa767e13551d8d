"""Batched small-matrix symmetric eigendecomposition — Pallas Jacobi kernel.

The factored 𝒮 path is built out of *stacks* of tiny symmetric PSD
eigenproblems: the per-view r×r score Grams of Phase 1, the d×d left Grams
of the joint-basis extraction, and the s×s Rayleigh–Ritz reductions of the
sketched joint path (``ajive``). On CPU these lower to LAPACK ``syevd`` per
matrix — fine. On TPU, XLA's ``eigh`` is a QDWH iteration designed for one
*large* matrix; a (B, n, n) stack of n ≤ 64 problems wants the opposite
shape: one resident program that sweeps every matrix in the batch in
lock-step. That is this kernel.

Algorithm: cyclic Jacobi with a **parallel (round-robin) ordering** — each
step applies n//2 disjoint Givens rotations simultaneously, so a full sweep
is ``n_steps = n-1`` (n even; odd n rides a phantom column) steps instead of
n(n-1)/2 serial rotations. A rotation step is expressed entirely in
MXU-friendly matrix algebra (no scatters, no dynamic row updates):

    J = I + P diag(c-1) Pᵀ + Q diag(c-1) Qᵀ + P diag(s) Qᵀ - Q diag(s) Pᵀ
    A ← Jᵀ A J,   V ← V J

where P/Q are the step's static one-hot pair embeddings (n, n_pairs) and
(c, s) come from Rutishauser's symmetric-Schur 2×2 solve on the current
(app, aqq, apq) diagonals, which needs only square roots and divides (the
TPU kernel compiler has no ``atan2``). Zero off-diagonals are pinned to
t = 0 so converged (and phantom) pairs are exact no-ops.

Convergence: cyclic Jacobi is globally convergent and asymptotically
quadratic; ``sweeps`` is a fixed compile-time count (default 12 — machine
precision for n ≤ 64 in fp32 with slack) so the program is shape-static and
scan/vmap-safe. Eigenvalues come back *ascending* with matching eigenvector
columns — the ``jnp.linalg.eigh`` convention — so the kernel is a drop-in
for the LAPACK path (eigenvector sign/rotation within degenerate clusters
is implementation-defined in both).

On the CPU the kernel runs in ``interpret=True`` mode (property
tests force it through ``ops.batched_small_eigh(force="jacobi")``); the
production CPU path stays on LAPACK via the ``ops`` wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import mxu

MAX_JACOBI_DIM = 64


def _round_robin_pairs(n: int):
    """Static parallel-Jacobi schedule: (n_steps, n_pairs) index arrays of
    disjoint (p, q) pairs covering every unordered pair once per sweep
    (circle method; odd n plays against a phantom seat that is filtered
    out, keeping n_pairs static across steps)."""
    m = n if n % 2 == 0 else n + 1          # phantom seat for odd n
    seats = list(range(m))
    steps_p, steps_q = [], []
    for _ in range(m - 1):
        ps, qs = [], []
        for i in range(m // 2):
            a, b = seats[i], seats[m - 1 - i]
            if a < n and b < n:             # drop phantom pairings
                ps.append(min(a, b))
                qs.append(max(a, b))
        steps_p.append(ps)
        steps_q.append(qs)
        # rotate all seats but the first
        seats = [seats[0]] + [seats[-1]] + seats[1:-1]
    return np.asarray(steps_p, np.int32), np.asarray(steps_q, np.int32)


def _schedule_onehots(n: int):
    """One-hot pair embeddings P, Q of shape (n_steps, n, n_pairs) for the
    round-robin schedule — static constants baked into the program."""
    p_idx, q_idx = _round_robin_pairs(n)
    n_steps, n_pairs = p_idx.shape
    p = np.zeros((n_steps, n, n_pairs), np.float32)
    q = np.zeros((n_steps, n, n_pairs), np.float32)
    for s in range(n_steps):
        p[s, p_idx[s], np.arange(n_pairs)] = 1.0
        q[s, q_idx[s], np.arange(n_pairs)] = 1.0
    return p, q


# Batched (b, ·, ·) contractions in the two forms the TPU kernel compiler
# lowers: A @ B and A @ Bᵀ, batch dim leading on both operands.
_NN = (((2,), (1,)), ((0,), (0,)))
_NT = (((2,), (2,)), ((0,), (0,)))


def _jacobi_sweeps(a, p_ref, q_ref, sweeps: int):
    """Run ``sweeps`` full parallel-Jacobi sweeps on a (bb, n, n) symmetric
    stack. ``p_ref``/``q_ref`` hold the (n_steps, n, n_pairs) schedule.
    Returns (diag, V) with A ≈ V diag(diag) Vᵀ, unsorted."""
    bb, n, _ = a.shape
    n_steps, _, n_pairs = p_ref.shape
    eye = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), (bb, n, n))

    def step(s, carry):
        a, v = carry
        idx = s % n_steps
        pm = jnp.broadcast_to(p_ref[idx], (bb, n, n_pairs))
        qm = jnp.broadcast_to(q_ref[idx], (bb, n, n_pairs))
        # pair diagonals a_pp, a_qq, a_pq as one-hot masked reductions
        ap = mxu.dot(a, pm, _NN)
        aq = mxu.dot(a, qm, _NN)
        app = jnp.sum(pm * ap, axis=1)
        aqq = jnp.sum(qm * aq, axis=1)
        apq = jnp.sum(pm * aq, axis=1)
        # Rutishauser's symmetric 2×2 Schur rotation (the smaller angle).
        # Exact-zero off-diagonals (converged / phantom pairs) rotate by 0.
        zero = apq == 0.0
        tau = (aqq - app) / (2.0 * jnp.where(zero, 1.0, apq))
        t = jnp.where(tau >= 0.0, 1.0, -1.0) / (
            jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
        t = jnp.where(zero, 0.0, t)
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        s_ = t * c
        # J = I + X Pᵀ + Y Qᵀ and Jᵀ = I + P Xᵀ + Q Yᵀ, with
        # X = P diag(c-1) - Q diag(s), Y = Q diag(c-1) + P diag(s)
        cm1 = (c - 1.0)[:, None, :]
        s3 = s_[:, None, :]
        x = pm * cm1 - qm * s3
        y = qm * cm1 + pm * s3
        j = eye + mxu.dot(x, pm, _NT) + mxu.dot(y, qm, _NT)
        jt = eye + mxu.dot(pm, x, _NT) + mxu.dot(qm, y, _NT)
        a = mxu.dot(jt, mxu.dot(a, j, _NN), _NN)
        # pin symmetry drift; Aᵀ = I·Aᵀ is the A @ Bᵀ form
        a = 0.5 * (a + mxu.dot(eye, a, _NT))
        v = mxu.dot(v, j, _NN)
        return a, v

    a, v = jax.lax.fori_loop(0, sweeps * n_steps, step, (a, eye))
    return jnp.sum(a * eye, axis=-1), v


def _kernel(a_ref, p_ref, q_ref, lam_out, vec_out, *, sweeps):
    a = a_ref[...].astype(jnp.float32)
    diag, v = _jacobi_sweeps(a, p_ref, q_ref, sweeps)
    lam_out[...] = diag
    vec_out[...] = v


@functools.partial(jax.jit, static_argnames=("sweeps", "block_b",
                                             "interpret"))
def jacobi_eigh(a, *, sweeps: int = 12, block_b: int = 8,
                interpret: bool = False):
    """Eigendecomposition of a (..., n, n) symmetric stack, n ≤ 64.

    Returns ``(lam, vec)`` with eigenvalues ascending and ``a ≈ vec @
    diag(lam) @ vecᵀ`` per batch element — the ``jnp.linalg.eigh``
    convention. The batch is tiled ``block_b`` matrices per grid cell; the
    trailing partial tile is masked by Pallas block clipping.
    """
    n = a.shape[-1]
    if a.shape[-2] != n:
        raise ValueError(f"square matrices required, got {a.shape}")
    if n > MAX_JACOBI_DIM:
        raise ValueError(f"jacobi_eigh handles n <= {MAX_JACOBI_DIM}, "
                         f"got n={n} (use jnp.linalg.eigh)")
    lead = a.shape[:-2]
    a3 = a.reshape((-1, n, n)).astype(jnp.float32)
    b = a3.shape[0]
    bb = min(block_b, b)
    p_oh, q_oh = _schedule_onehots(n)
    n_steps, _, n_pairs = p_oh.shape
    lam, vec = pl.pallas_call(
        functools.partial(_kernel, sweeps=sweeps),
        grid=(pl.cdiv(b, bb),),
        in_specs=[pl.BlockSpec((bb, n, n), lambda i: (i, 0, 0)),
                  pl.BlockSpec((n_steps, n, n_pairs), lambda i: (0, 0, 0)),
                  pl.BlockSpec((n_steps, n, n_pairs), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((bb, n), lambda i: (i, 0)),
                   pl.BlockSpec((bb, n, n), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, n), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, n), jnp.float32)],
        interpret=interpret,
    )(a3, jnp.asarray(p_oh), jnp.asarray(q_oh))
    order = jnp.argsort(lam, axis=-1)
    lam = jnp.take_along_axis(lam, order, axis=-1)
    vec = jnp.take_along_axis(vec, order[:, None, :], axis=-1)
    return lam.reshape(lead + (n,)), vec.reshape(lead + (n, n))

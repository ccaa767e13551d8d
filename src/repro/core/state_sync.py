"""State synchronization protocols 𝒮 (Definition 3.3 + Algorithm 1 line 12).

Inputs are *stacked* per-client projected second moments ṽ (leading client
axis) plus the shared per-round basis R_k reconstructed from the broadcast
seed. Protocols:

  none      — clients reinitialize adaptive states each round (most fed-LoRA).
  avg       — naive weighted averaging of ṽ (the FedOpt-style baseline that
              Appendix F shows is biased by squared drift).
  avg_svd   — naive average followed by rank-r SVD re-projection.
  ajive     — the paper's protocol: lift views V^i = ṽ^i R_kᵀ, extract the
              joint component via AJIVE (joint rank = r), broadcast.

All return the *lifted* (n, n_cols) synchronized state; the caller re-projects
onto each client's next-round basis (InitState, Eq. 5).

Factored fast path: every protocol input has rank ≤ r, so the lift → sync →
re-project round-trip closes over the projected coordinates.
:func:`sync_block_factored` runs the same protocols without ever building a
dense ``(m, n)`` view — weighted averaging commutes with the (linear) lift,
rank-r SVD re-projection of a rank-≤r lift is the identity (making
``avg_svd`` ≡ ``avg`` in factored form), AJIVE runs on the (C·r) score space
(`ajive.ajive_sync_factored`), and the old→new basis change is the r×r
transfer ``projector.reproject``. Requires the shared-basis invariant of the
seeded-broadcast protocol (Appendix D).

Heterogeneous bases (the adaptive round 0, or data-driven refresh modes):
the shared-basis cancellation fails, but :func:`sync_block_hetero_factored`
still closes the round-trip over per-client r×r transfer Grams ``Q_iᵀ Q_0``
— averaging picks up the transfer directly, rank-r SVD factors through the
(C·r)×(C·r) Grams of the two skinny lift factors, and AJIVE composes the
basis change into its score Gram (`ajive.ajive_sync_hetero_factored`). No
round program executes a dense lift; :func:`sync_block` and the per-client
dense lift (`core.reference.dense_sync_block`) remain as references.

Chunk-streamed rounds (``core.fed`` / ``launch.steps`` with ``client_chunk``)
assemble the full (C, ·, r) ṽ/basis stacks from per-chunk outputs before
calling any protocol here — every 𝒮 input is the complete cohort uplink
(O(C·r·dim), the factored payload, never a dense view), which keeps the
synchronized result independent of the chunk size.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .ajive import (_inv_sqrt_rank_safe, ajive_sync, ajive_sync_factored,
                    ajive_sync_hetero_factored, normalize_weights)
from . import aggregation as agg
from . import projector as proj

PyTree = Any


def lift_views(v_stack: jnp.ndarray, basis: jnp.ndarray, side: str) -> jnp.ndarray:
    """ṽ (K, m, r) + basis (n, r) -> views (K, m, n) [right side]; left is
    (K, r, n) + (m, r) -> (K, m, n)."""
    if side == proj.RIGHT:
        return jnp.einsum("kmr,nr->kmn", v_stack, basis)
    return jnp.einsum("mr,krn->kmn", basis, v_stack)


def project_state(lifted: jnp.ndarray, basis: jnp.ndarray, side: str) -> jnp.ndarray:
    """Re-project a lifted (m, n) state onto a (possibly new) basis."""
    if side == proj.RIGHT:
        return lifted @ basis                  # (m,n)@(n,r) -> (m,r)
    return basis.T @ lifted                    # (r,m)@(m,n) -> (r,n)


def sync_none(v_stack, basis, side, weights=None, rank: Optional[int] = None):
    return None


def sync_avg(v_stack, basis, side, weights=None, rank: Optional[int] = None):
    w = normalize_weights(weights, v_stack.shape[0])
    views = lift_views(v_stack.astype(jnp.float32), basis, side)
    return jnp.einsum("k,kmn->mn", w, views)


def sync_avg_svd(v_stack, basis, side, weights=None, rank: Optional[int] = None):
    avg = sync_avg(v_stack, basis, side, weights)
    r = rank if rank is not None else basis.shape[1]
    u, s, vt = jnp.linalg.svd(avg, full_matrices=False)
    return (u[:, :r] * s[:r][None, :]) @ vt[:r]


def sync_ajive(v_stack, basis, side, weights=None, rank: Optional[int] = None):
    """The paper's 𝒮: spectral shared-signal extraction across client views."""
    r = rank if rank is not None else basis.shape[1]
    views = lift_views(v_stack.astype(jnp.float32), basis, side)
    w = None if weights is None else jnp.asarray(weights, jnp.float32)
    return ajive_sync(views, rank=r, weights=w)


SYNC_PROTOCOLS = {
    "none": sync_none,
    "avg": sync_avg,
    "avg_svd": sync_avg_svd,
    "ajive": sync_ajive,
}


def sync_lifted_views(protocol: str, views: jnp.ndarray, weights=None,
                      rank: Optional[int] = None) -> jnp.ndarray:
    """Run protocol 𝒮 on *already-lifted* (k, m, n) views — the dense
    reference dispatch shared by the engine and the sharded runtime (used
    when clients lifted with heterogeneous bases, where the factored path
    does not apply)."""
    if protocol == "ajive":
        return ajive_sync(views, rank=rank, weights=weights)
    avg = jnp.einsum("k,kmn->mn", normalize_weights(weights, views.shape[0]),
                     views)
    if protocol == "avg":
        return avg
    if protocol == "avg_svd":
        u, s, vt = jnp.linalg.svd(avg, full_matrices=False)
        return (u[:, :rank] * s[:rank][None, :]) @ vt[:rank]
    raise ValueError(protocol)


def sync_block(protocol: str, v_stack: jnp.ndarray, old_basis: jnp.ndarray,
               new_basis: jnp.ndarray, side: str, weights=None,
               rank: Optional[int] = None) -> Optional[jnp.ndarray]:
    """One adapted block end-to-end: lift with the round-k basis, synchronize,
    re-project onto the round-(k+1) basis. Returns the next-round ṽ init, or
    None for protocol='none' (clients zero-init).

    This is the dense reference path (materializes (k, m, n) views); the
    production round loop uses :func:`sync_block_factored`.
    """
    lifted = SYNC_PROTOCOLS[protocol](v_stack, old_basis, side, weights, rank)
    if lifted is None:
        return None
    return jnp.maximum(project_state(lifted, new_basis, side), 0.0)


def sync_block_synced_factored(protocol: str, v_stack: jnp.ndarray, side: str,
                               weights=None,
                               rank: Optional[int] = None,
                               exclude_zero_weights: bool = False,
                               robust: str = "none", trim: float = 0.2,
                               iters: int = 8, tol: float = 1e-6
                               ) -> Optional[jnp.ndarray]:
    """Run protocol 𝒮 in projected coordinates (no lift): returns the synced
    state expressed on the *round-k* basis, or None for 'none'.

    ``exclude_zero_weights`` is the participation-masked round's 𝒮 contract:
    clients carrying zero aggregation weight (dropped / straggling this
    round) are excluded from the AJIVE joint-basis estimate, not just from
    the final weighted mean (averaging protocols exclude them already —
    zero weights vanish from a weighted mean).

    ``robust`` extends the 𝒜-side defense (``FedConfig.robust_agg``) to the
    projected-moment stacks: the protocol's final weighted mean over the
    (C, ·, r) stack is replaced by the matching
    :func:`aggregation.robust_factored_reduce` mode (trimmed-mean /
    geomedian / norm-clip in factored coordinates), so one poisoned moment
    upload cannot drag the synchronized state every honest client inherits.
    ``robust='none'`` is EXACTLY the unguarded reduction — the guarded
    program's honest-cohort bit-identity hinges on this."""
    if protocol == "none":
        return None
    if protocol in ("avg", "avg_svd"):
        # Lift is linear ⇒ averaging commutes with it; the rank-r SVD
        # re-projection in avg_svd is the identity on a rank-≤r lift.
        if robust != "none":
            return agg.robust_factored_reduce(v_stack, weights, robust,
                                              trim=trim, iters=iters, tol=tol)
        w = normalize_weights(weights, v_stack.shape[0])
        return jnp.einsum("k,k...->...", w, v_stack.astype(jnp.float32))
    if protocol == "ajive":
        r = rank if rank is not None else (
            v_stack.shape[-1] if side == proj.RIGHT else v_stack.shape[-2])
        return ajive_sync_factored(v_stack, rank=r, weights=weights, side=side,
                                   exclude_zero_weights=exclude_zero_weights,
                                   robust=robust, trim=trim, iters=iters,
                                   tol=tol)
    raise ValueError(protocol)


# ------------------------------------------- heterogeneous-basis factored --

def transfer_grams(b_stack: jnp.ndarray) -> jnp.ndarray:
    """Per-client r×r basis-change transfers ``T_i = Q_iᵀ Q_0`` onto the
    reference (client-0) basis. b_stack (C, dim, r) -> (C, r, r)."""
    b32 = b_stack.astype(jnp.float32)
    return jnp.einsum("cdr,ds->crs", b32, b32[0])


def _gram_orth(gram: jnp.ndarray):
    """Rank-safe orthonormalization of a factor ``X`` from its Gram ``XᵀX``:
    returns (coeff, rfac) with ``Q = X @ coeff`` orthonormal (numerically-null
    directions zeroed) and ``X = Q @ rfac``."""
    lam, vec = jnp.linalg.eigh(gram)
    lam = jnp.maximum(lam[::-1], 0.0)
    vec = vec[:, ::-1]
    coeff = vec * _inv_sqrt_rank_safe(lam)[None, :]
    rfac = (vec * jnp.sqrt(lam)[None, :]).T
    return coeff, rfac


def _hetero_avg_svd(v32, b32, w, rank, side):
    """Rank-``rank`` SVD of the weighted average of heterogeneously-lifted
    views, projected onto the client-0 basis — via the two skinny factors of
    ``A = Σ wᵢ lift(ṽ^i, Q_i)`` and their (C·r)×(C·r) Grams, never forming
    the dense (m, n) average."""
    c, r = v32.shape[0], b32.shape[-1]
    t_stack = transfer_grams(b32).reshape(c * r, r)        # Ĉᵀ Q_0
    if side == proj.RIGHT:
        # A = Û Ĉᵀ, Û = [wᵢ ṽ^i] (m, C·r), Ĉ = [Q_i] (n, C·r)
        uhat = jnp.moveaxis(w[:, None, None] * v32, 0, 1).reshape(
            v32.shape[1], c * r)
        chat = jnp.moveaxis(b32, 0, 1).reshape(b32.shape[1], c * r)
        cu, ru = _gram_orth(uhat.T @ uhat)
        cc, rc = _gram_orth(chat.T @ chat)
        p, s, wt = jnp.linalg.svd(ru @ rc.T)               # middle (C·r)²
        left = uhat @ (cu @ p[:, :rank])                   # Q_u P_r, (m, rank)
        right = wt[:rank] @ (cc.T @ t_stack)               # W_rᵀ Q_cᵀ Q_0
        return (left * s[:rank][None, :]) @ right          # (m, r)
    # A = Ĉ V̂, Ĉ = [Q_i] (m, C·r), V̂ = [wᵢ ṽ^i] stacked rows (C·r, n)
    chat = jnp.moveaxis(b32, 0, 1).reshape(b32.shape[1], c * r)
    vhat = (w[:, None, None] * v32).reshape(c * r, v32.shape[-1])
    cc, rc = _gram_orth(chat.T @ chat)
    cv, rv = _gram_orth(vhat @ vhat.T)
    p, s, wt = jnp.linalg.svd(rc @ rv.T)
    left = t_stack.T @ (cc @ p[:, :rank])                  # Q_0ᵀ Q_c P_r
    right = (wt[:rank] @ cv.T) @ vhat                      # W_rᵀ Q_vᵀ, (rank, n)
    return (left * s[:rank][None, :]) @ right              # (r, n)


def sync_block_hetero_factored(protocol: str, v_stack: jnp.ndarray,
                               b_stack: jnp.ndarray, side: str, weights=None,
                               rank: Optional[int] = None,
                               exclude_zero_weights: bool = False,
                               robust: str = "none", trim: float = 0.2,
                               iters: int = 8, tol: float = 1e-6
                               ) -> Optional[jnp.ndarray]:
    """Factored 𝒮 for **heterogeneous client bases** (the adaptive round-0
    case): each client lifted with its own basis, so the shared-basis
    cancellation of :func:`sync_block_synced_factored` does not apply — but
    the lift → 𝒮 → re-project-onto-client-0 round-trip still closes over r×r
    transfer Grams ``Q_iᵀ Q_0`` (see :func:`ajive_sync_hetero_factored`),
    eliminating the last dense per-client lift. Returns the synced state in
    projected shape on the client-0 basis (the dense per-client-lift
    :func:`sync_block`-style oracle's output), or None for 'none'.

    ``robust`` mirrors :func:`sync_block_synced_factored`: for the averaging
    protocols the moment stacks are first re-based onto the client-0
    coordinates (:func:`aggregation.rebase_factored_stack` — basis-coherent
    robust statistics under diverged bases) and then robustly reduced.
    Robust avg_svd reduces on the re-based coordinates, where every stack
    row is already rank ≤ r on the reference subspace, so the rank-r SVD
    re-projection is the identity and the mode coincides with robust avg
    (the out-of-subspace residual a robust vote cannot adjudicate is
    dropped). AJIVE's joint output is already expressed on client 0, so its
    final reduction robustifies directly."""
    if protocol == "none":
        return None
    if v_stack.ndim == 4:                      # stacked scan blocks (C,nb,·,r)
        return jax.vmap(
            lambda vs, bs: sync_block_hetero_factored(protocol, vs, bs, side,
                                                      weights, rank,
                                                      exclude_zero_weights,
                                                      robust, trim, iters,
                                                      tol),
            in_axes=1, out_axes=0)(v_stack, b_stack)
    r = b_stack.shape[-1]
    rank = rank if rank is not None else r
    v32 = v_stack.astype(jnp.float32)
    b32 = b_stack.astype(jnp.float32)
    w = normalize_weights(weights, v_stack.shape[0])
    if protocol == "ajive":
        return ajive_sync_hetero_factored(
            v32, b32, rank, weights, side,
            exclude_zero_weights=exclude_zero_weights,
            robust=robust, trim=trim, iters=iters, tol=tol)
    if robust != "none" and protocol in ("avg", "avg_svd"):
        based = agg.rebase_factored_stack(v32, b32, side)
        return agg.robust_factored_reduce(based, weights, robust,
                                          trim=trim, iters=iters, tol=tol)
    if protocol == "avg":
        t = transfer_grams(b32)                            # (C, r, r)
        if side == proj.RIGHT:
            return jnp.einsum("c,cmr,crs->ms", w, v32, t)
        return jnp.einsum("c,crs,crn->sn", w, t, v32)
    if protocol == "avg_svd":
        return _hetero_avg_svd(v32, b32, w, rank, side)
    raise ValueError(protocol)


def map_sync_leaves(leaf_fn, v_leaves, b_leaves):
    """Apply ``leaf_fn(v_stack, b_stack) -> synced`` over parallel per-leaf
    lists, one **vmapped program per shape bucket**.

    The per-leaf 𝒮 programs of a real model tree are overwhelmingly
    shape-identical (every attention block contributes the same (C, m, r)
    right leaf); running them one-by-one re-emits the same Gram → eigh →
    joint-basis chain per leaf and serializes the tiny solves. Bucketing by
    ``(v.shape, v.dtype, b.shape, b.dtype)`` — mirroring the PR-1 refresh
    bucketing (`galore.bucket_by_shape`) — stacks each bucket and emits the
    chain once under ``jax.vmap``, so the r×r eigendecompositions lower as
    one batched solve (kernel-routed on TPU). :func:`map_sync_leaves_per_leaf`
    is its reference.

    ``None`` v-leaves (non-adapted blocks) pass through as ``None``.
    ``leaf_fn`` must not return ``None`` (dispatch protocol='none' before
    calling). Singleton buckets skip the vmap wrapper entirely.
    """
    from .galore import bucket_by_shape
    out = [None] * len(v_leaves)
    keys = [None if v is None else
            (tuple(v.shape), str(v.dtype), tuple(b.shape), str(b.dtype))
            for v, b in zip(v_leaves, b_leaves)]
    buckets, _ = bucket_by_shape(keys)
    for _, idxs in buckets:
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = leaf_fn(v_leaves[i], b_leaves[i])
            continue
        vs = jnp.stack([v_leaves[i] for i in idxs])
        bs = jnp.stack([b_leaves[i] for i in idxs])
        res = jax.vmap(leaf_fn)(vs, bs)
        for j, i in enumerate(idxs):
            out[i] = res[j]
    return out


def map_sync_leaves_per_leaf(leaf_fn, v_leaves, b_leaves):
    """Reference for :func:`map_sync_leaves`: ``leaf_fn`` on one leaf at a
    time, ``None`` v-leaves passing through."""
    return [None if v is None else leaf_fn(v, b)
            for v, b in zip(v_leaves, b_leaves)]


def sync_block_factored(protocol: str, v_stack: jnp.ndarray,
                        old_basis: jnp.ndarray, new_basis: jnp.ndarray,
                        side: str, weights=None,
                        rank: Optional[int] = None) -> Optional[jnp.ndarray]:
    """Factored counterpart of :func:`sync_block`: synchronize in projected
    coordinates, then change basis with the r×r transfer — the dense (m, n)
    lift is never built. Assumes the shared-basis invariant (all clients hold
    the same seeded round-k basis)."""
    synced = sync_block_synced_factored(protocol, v_stack, side, weights, rank)
    if synced is None:
        return None
    return jnp.maximum(proj.reproject(synced, old_basis, new_basis, side), 0.0)

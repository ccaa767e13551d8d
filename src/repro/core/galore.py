"""GaLoreAdamW — gradient-subspace AdamW (paper §5 + Appendix A.1).

For each *target block* ``W ∈ R^{m×n}`` the optimizer keeps a rank-r basis and
AdamW moments in the projected shape (``(m,r)`` right / ``(r,n)`` left), never
materializing dense ``m×n`` states:

    g̃  = project(g, B)                      # MXU GEMM
    m̃  = β₁ m̃ + (1-β₁) g̃
    ṽ  = β₂ ṽ + (1-β₂) g̃²
    ũ  = m̂ / (√v̂ + ε)                       # bias-corrected
    u  = project_back(ũ, B)                 # MXU GEMM
    W ← W - η u - η λ W                      # ambient-space AdamW step

The projector refreshes every ``τ`` steps: data-driven (RSVD/SVD of the current
gradient) for the first ``S`` refreshes, then **seeded random orthonormal** —
the basis is a pure function of ``(s_k, refresh_idx, block_id)`` so the server
only ever broadcasts the integer seed (Appendix D). On refresh the buffers are
re-expressed with the r×r transfer ``B_oldᵀ B_new`` (Appendix A.1).

Non-target leaves (biases, norms) fall back to dense AdamW moments.

Execution paths
---------------
The default ``update`` is the **fused, shape-bucketed** path: target blocks
with identical (shape, rank) form one bucket whose basis/moment state is
stacked and whose trace-heavy machinery — the projector refresh (QR / RSVD /
refresh-mode cond) and, on TPU, the fused optimizer kernel — is emitted once
per bucket (vmapped over the stacked leading dim), so trace size and compile
time stop scaling linearly with leaf count. On TPU the per-bucket step lowers
to the fused Pallas kernel (``kernels.galore_adamw.galore_precond_step``) —
one VMEM-resident pass with no dense HBM round-trips between optimizer
stages. On CPU/GPU-jnp the cheap GEMM+Adam chain stays per leaf (reading each
dense gradient exactly once beats a stack/unstack round-trip) and XLA fuses
the projected-space elementwise chain. ``GaloreConfig.use_pallas`` forces
the kernel on/off (None = auto: ``kernels.ops.use_kernels`` — on CPU the
kernel still runs, in interpret mode, when forced on, which is what the
parity tests use).

The per-leaf loops the bucketed paths replaced stay as plain reference
functions that tests call: :func:`galore_transform_update_per_leaf` (the
step) and :func:`manual_refresh_per_leaf` (the round-boundary refresh).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import projector as proj
from ..kernels import ops as kops
from ..optim.base import GradientTransformation

PyTree = Any


class GaloreBlockState(NamedTuple):
    basis: jnp.ndarray   # (dim, r) fp32, orthonormal columns
    m: jnp.ndarray       # projected first moment, fp32
    v: jnp.ndarray       # projected second moment, fp32 (elementwise)


class DenseMoments(NamedTuple):
    m: jnp.ndarray
    v: jnp.ndarray


class GaloreState(NamedTuple):
    count: jnp.ndarray   # int32 step counter
    seed: jnp.ndarray    # uint32 round seed s_k (server-broadcast)
    blocks: PyTree       # per-leaf GaloreBlockState | DenseMoments


def default_target_fn(path: str, leaf: jnp.ndarray) -> bool:
    """Target = any matrix leaf (attention/MLP projections). 3-D leaves are
    stacked scan blocks: one independent projector per layer (leading dim)."""
    return leaf.ndim in (2, 3)


@dataclasses.dataclass(frozen=True)
class GaloreConfig:
    rank: int = 8
    refresh_every: int = 200          # tau
    adaptive_steps: int = 2           # S data-driven refreshes, then random
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    oversample: int = 8
    use_exact_svd: bool = False
    # 'auto': lax.cond picks RSVD vs random by refresh index (both lowered)
    # 'random': only the seeded-random branch is compiled (production dry-run)
    # 'svd': only the data-driven branch (warmup-phase step function)
    refresh_mode: str = "auto"
    bias_correction: bool = True
    # The bucketed step's kernel route (module docstring): None = auto
    # (kernels.ops.use_kernels); True forces the kernel (interpret mode
    # off-TPU).
    use_pallas: Optional[bool] = None
    pallas_block_rows: int = 128


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _block_rank(cfg: GaloreConfig, shape) -> int:
    return min(cfg.rank, min(shape[-2:]))


def _proj_shape(shape, rank: int, side: str):
    """Projected buffer shape, preserving leading stacked dims."""
    lead = tuple(shape[:-2])
    m, n = shape[-2:]
    return lead + ((m, rank) if side == proj.RIGHT else (rank, n))


def _block_keys(seed, refresh_idx, block_id, lead_shape):
    """One key for a 2-D block; per-layer keys for stacked (nb, m, n) blocks."""
    key = proj.seeded_block_key(seed, refresh_idx, block_id)
    if not lead_shape:
        return key
    return proj.stacked_keys(key, lead_shape[0])


def galore_init(cfg: GaloreConfig, params: PyTree,
                target_fn: Callable = default_target_fn,
                seed: int = 0) -> GaloreState:
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    block_states = []
    for block_id, (path, p) in enumerate(leaves):
        if target_fn(_path_str(path), p) and p.ndim >= 2:
            side = proj.proj_side(p.shape)
            r = _block_rank(cfg, p.shape)
            dim = proj.basis_dim(p.shape)
            keys = _block_keys(jnp.uint32(seed), jnp.uint32(0), block_id,
                               p.shape[:-2])
            basis = proj.random_basis_nd(keys, dim, r)
            pshape = _proj_shape(p.shape, r, side)
            block_states.append(GaloreBlockState(
                basis=basis,
                m=jnp.zeros(pshape, jnp.float32),
                v=jnp.zeros(pshape, jnp.float32)))
        else:
            block_states.append(DenseMoments(
                m=jnp.zeros(p.shape, jnp.float32),
                v=jnp.zeros(p.shape, jnp.float32)))
    return GaloreState(count=jnp.zeros([], jnp.int32),
                       seed=jnp.asarray(seed, jnp.uint32),
                       blocks=jax.tree_util.tree_unflatten(treedef, block_states))


def _refresh_basis(cfg: GaloreConfig, g32, old: GaloreBlockState,
                   refresh_idx, seed, block_id, side, rank):
    dim = proj.basis_dim(g32.shape)
    keys = _block_keys(seed, refresh_idx, block_id, g32.shape[:-2])

    def random_branch(_):
        return proj.random_basis_nd(keys, dim, rank)

    def data_branch(_):
        if cfg.use_exact_svd:
            return proj.svd_basis_nd(g32, rank, side)
        return proj.rsvd_basis_nd(g32, rank, side, keys, cfg.oversample)

    if cfg.refresh_mode == "random":
        new_basis = random_branch(None)
    elif cfg.refresh_mode == "svd":
        new_basis = data_branch(None)
    else:
        new_basis = jax.lax.cond(refresh_idx < cfg.adaptive_steps,
                                 data_branch, random_branch, operand=None)
    m = proj.reproject(old.m, old.basis, new_basis, side)
    # ṽ is an elementwise second moment; the change-of-basis transfer is the
    # paper's Appendix A.1 rule — clamp to keep the sqrt well-defined.
    v = jnp.maximum(proj.reproject(old.v, old.basis, new_basis, side), 0.0)
    return GaloreBlockState(basis=new_basis, m=m, v=v)


def _projected_adam(cfg: GaloreConfig, gt, m, v, count):
    """The shared projected-space Adam chain: moment EMAs + (optionally
    bias-corrected) update direction. Single source of truth for both the
    bucketed step and its per-leaf reference."""
    m = cfg.b1 * m + (1 - cfg.b1) * gt
    v = cfg.b2 * v + (1 - cfg.b2) * gt * gt
    if cfg.bias_correction:
        c = count.astype(jnp.float32)
        c1 = 1 - cfg.b1 ** c
        c2 = 1 - cfg.b2 ** c
    else:
        c1 = c2 = 1.0
    ut = (m / c1) / (jnp.sqrt(v / c2) + cfg.eps)
    return m, v, ut


def _block_update(cfg: GaloreConfig, g, st: GaloreBlockState, count,
                  refresh_idx, do_refresh, seed, block_id,
                  project_back: bool = True):
    side = proj.proj_side(g.shape)
    rank = st.basis.shape[-1]
    g32 = g.astype(jnp.float32)

    st = jax.lax.cond(
        do_refresh,
        lambda s: _refresh_basis(cfg, g32, s, refresh_idx, seed, block_id,
                                 side, rank),
        lambda s: s, st)

    gt = proj.project(g32, st.basis, side)
    m, v, ut = _projected_adam(cfg, gt, st.m, st.v, count)
    u = proj.project_back(ut, st.basis, side) if project_back else ut
    return u, GaloreBlockState(basis=st.basis, m=m, v=v)


def _dense_update(cfg: GaloreConfig, g, st: DenseMoments, count):
    m, v, u = _projected_adam(cfg, g.astype(jnp.float32), st.m, st.v, count)
    return u, DenseMoments(m=m, v=v)


def _resolve_use_pallas(cfg: GaloreConfig) -> bool:
    if cfg.use_pallas is not None:
        return cfg.use_pallas
    return kops.use_kernels()


def _bucketed_update(cfg: GaloreConfig, use_pallas: bool, g_leaves,
                     blk_leaves, count, refresh_idx, do_refresh, seed,
                     project_back: bool = True):
    """Shape-bucketed batched GaLore step.

    Target blocks with identical (shape, rank) share one stacked state bucket:
    the refresh (QR/RSVD + mode cond — the dominant trace cost) is emitted
    once per bucket, vmapped, and the Pallas kernel path consumes the whole
    bucket in one batched call. Per-block seeded keys fold in the *original*
    leaf index, so every basis is the one :func:`galore_transform_update_per_leaf`
    draws (the server-broadcast-a-seed protocol is unaffected by bucketing).
    ``project_back=False`` keeps the update in projected coordinates (ũ,
    shaped like the moments) — the factored-delta client path, where the
    ambient lift is deferred to the weight read.
    """
    n_leaves = len(blk_leaves)
    updates = [None] * n_leaves
    new_blocks = [None] * n_leaves

    buckets: dict = {}
    for i, (g, st) in enumerate(zip(g_leaves, blk_leaves)):
        if isinstance(st, GaloreBlockState):
            buckets.setdefault((tuple(g.shape), int(st.basis.shape[-1])),
                               []).append(i)
        else:
            updates[i], new_blocks[i] = _dense_update(cfg, g, st, count)

    for (shape, rank), idxs in sorted(buckets.items()):
        side = proj.proj_side(shape)
        lead = shape[:-2]
        dim = proj.basis_dim(shape)

        def stacked_g(idxs=idxs):
            # Materialized only where the batched form pays for the copy:
            # inside the (rare) data-driven refresh branch and the Pallas
            # kernel call. The jnp hot path reads the leaves directly.
            return jnp.stack([g_leaves[i] for i in idxs]).astype(jnp.float32)

        basis = jnp.stack([blk_leaves[i].basis for i in idxs])
        m = jnp.stack([blk_leaves[i].m for i in idxs])
        v = jnp.stack([blk_leaves[i].v for i in idxs])
        block_ids = jnp.asarray(idxs, jnp.uint32)

        def bucket_keys(block_ids=block_ids, lead=lead):
            keys = jax.vmap(lambda bid: proj.seeded_block_key(
                seed, refresh_idx, bid))(block_ids)
            if lead:
                keys = jax.vmap(
                    lambda kk: proj.stacked_keys(kk, lead[0]))(keys)
            return keys

        def random_branch(_, dim=dim, rank=rank, bucket_keys=bucket_keys):
            return proj.random_basis_nd(bucket_keys(), dim, rank)

        def data_branch(_, stacked_g=stacked_g, rank=rank, side=side,
                        bucket_keys=bucket_keys):
            if cfg.use_exact_svd:
                return proj.svd_basis_nd(stacked_g(), rank, side)
            return proj.rsvd_basis_nd(stacked_g(), rank, side, bucket_keys(),
                                      cfg.oversample)

        def refresh(args, side=side, random_branch=random_branch,
                    data_branch=data_branch):
            b_old, m_old, v_old = args
            if cfg.refresh_mode == "random":
                b_new = random_branch(None)
            elif cfg.refresh_mode == "svd":
                b_new = data_branch(None)
            else:
                b_new = jax.lax.cond(refresh_idx < cfg.adaptive_steps,
                                     data_branch, random_branch, operand=None)
            m_new = proj.reproject(m_old, b_old, b_new, side)
            v_new = jnp.maximum(proj.reproject(v_old, b_old, b_new, side), 0.0)
            return b_new, m_new, v_new

        basis, m, v = jax.lax.cond(do_refresh, refresh, lambda a: a,
                                   (basis, m, v))

        if use_pallas:
            # One fused VMEM-resident pass per bucket (vmapped over the
            # bucket's leading dim -> an extra grid dimension, not a loop).
            # Stacking the gradients costs one extra read/write of g, which
            # the kernel's saved inter-stage HBM round-trips repay. With
            # project_back=False the kernel skips the final lift GEMM and
            # emits ũ in the moment shape.
            u, m, v = kops.galore_precond_step(
                stacked_g(), basis, m, v, count.astype(jnp.float32),
                side=side, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
                block_rows=cfg.pallas_block_rows,
                bias_correction=cfg.bias_correction,
                project_back=project_back)
            for j, i in enumerate(idxs):
                updates[i] = u[j]
                new_blocks[i] = GaloreBlockState(basis=basis[j], m=m[j],
                                                 v=v[j])
            continue

        # jnp hot path: the trace-heavy refresh above is shared per bucket;
        # the cheap GEMM+Adam chain stays per leaf so the dense gradient is
        # read exactly once (no O(leaf·m·n) stack/unstack round-trip — XLA
        # fuses the projected-space elementwise chain between the two GEMMs).
        for j, i in enumerate(idxs):
            gt = proj.project(g_leaves[i].astype(jnp.float32), basis[j], side)
            mj, vj, ut = _projected_adam(cfg, gt, m[j], v[j], count)
            updates[i] = (proj.project_back(ut, basis[j], side)
                          if project_back else ut)
            new_blocks[i] = GaloreBlockState(basis=basis[j], m=mj, v=vj)

    return updates, new_blocks


def _is_block(x) -> bool:
    return isinstance(x, (GaloreBlockState, DenseMoments))


def _step_operands(cfg: GaloreConfig, grads, state: GaloreState):
    """The shared head of one GaLore step: the advanced count, the in-step
    ``count % τ`` refresh predicate and index, and the flattened gradient
    and block-state leaves."""
    count = state.count + 1
    refresh_idx = state.count // cfg.refresh_every
    do_refresh = (state.count % cfg.refresh_every) == 0
    leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
    treedef = jax.tree_util.tree_structure(grads)
    blk_leaves = jax.tree_util.tree_leaves(state.blocks, is_leaf=_is_block)
    return count, refresh_idx, do_refresh, leaves, treedef, blk_leaves


def _step_result(state: GaloreState, count, treedef, updates, new_blocks):
    return (jax.tree_util.tree_unflatten(treedef, updates),
            GaloreState(count=count, seed=state.seed,
                        blocks=jax.tree_util.tree_unflatten(treedef,
                                                            new_blocks)))


@jax.named_scope("galore.update")
def galore_transform_update(cfg: GaloreConfig, grads, state: GaloreState,
                            project_back: bool = True,
                            projected: bool = False):
    """One GaLore preconditioning step as a pure function (the
    ``scale_by_galore`` update body): in-step ``count % τ`` refresh, projected
    Adam moments, update direction, shape-bucketed (module docstring). With
    the default ``project_back=True`` target-block updates are lifted back to
    ambient shape (the dense chain API). ``project_back=False`` returns them
    as the *projected* ũ (shaped like the moments) — the factored-delta
    client path, which keeps the whole local step in rank-r coordinates and
    defers the lift to the weight read. Non-target (``DenseMoments``) leaves
    are plain Adam either way.

    ``projected=True`` is the **lift-free** consumption mode: the incoming
    gradients are *already* in rank-r coordinates (the projected-cotangent
    VJP of the delta-aware forward), so the ``Pᵀg`` projection GEMM is
    skipped and the step is pure projected-space Adam. The caller owns the
    refresh (hoisted :func:`maybe_refresh_instep` before the forward, so the
    cotangents arrive on the refreshed basis); every leaf must be a target
    block (:func:`all_blocks_projected`)."""
    count, refresh_idx, do_refresh, leaves, treedef, blk_leaves = \
        _step_operands(cfg, grads, state)
    if projected:
        updates, new_blocks = [], []
        for (path, g), st in zip(leaves, blk_leaves):
            if not isinstance(st, GaloreBlockState):
                raise ValueError(
                    "projected-gradient GaLore step requires every leaf to "
                    f"be a target block; {_path_str(path)} is dense")
            side = _moment_side(st)
            m, v, ut = _projected_adam(cfg, g.astype(jnp.float32), st.m,
                                       st.v, count)
            updates.append(proj.project_back(ut, st.basis, side)
                           if project_back else ut)
            new_blocks.append(GaloreBlockState(basis=st.basis, m=m, v=v))
        return _step_result(state, count, treedef, updates, new_blocks)
    updates, new_blocks = _bucketed_update(
        cfg, _resolve_use_pallas(cfg), [g for _, g in leaves],
        blk_leaves, count, refresh_idx, do_refresh, state.seed,
        project_back=project_back)
    return _step_result(state, count, treedef, updates, new_blocks)


def galore_transform_update_per_leaf(cfg: GaloreConfig, grads,
                                     state: GaloreState,
                                     project_back: bool = True):
    """Reference for :func:`galore_transform_update`: the same step as a
    per-leaf loop — one refresh cond and one projected Adam chain per target
    block, each block keyed by its own leaf index. Tests hold the bucketed
    step to it."""
    count, refresh_idx, do_refresh, leaves, treedef, blk_leaves = \
        _step_operands(cfg, grads, state)
    updates, new_blocks = [], []
    for block_id, ((_, g), st) in enumerate(zip(leaves, blk_leaves)):
        if isinstance(st, GaloreBlockState):
            u, nst = _block_update(cfg, g, st, count, refresh_idx,
                                   do_refresh, state.seed, block_id,
                                   project_back=project_back)
        else:
            u, nst = _dense_update(cfg, g, st, count)
        updates.append(u)
        new_blocks.append(nst)
    return _step_result(state, count, treedef, updates, new_blocks)


def scale_by_galore(cfg: GaloreConfig,
                    target_fn: Callable = default_target_fn,
                    seed: int = 0) -> GradientTransformation:
    """GaLore preconditioning as a GradientTransformation (chain with weight
    decay + lr like AdamW), on the shape-bucketed step."""

    def init(params):
        return galore_init(cfg, params, target_fn, seed)

    def update(grads, state, params=None):
        del params
        return galore_transform_update(cfg, grads, state, project_back=True)

    return GradientTransformation(init, update)


def galore_adamw(cfg: GaloreConfig, learning_rate, weight_decay: float = 0.01,
                 target_fn: Callable = default_target_fn, seed: int = 0,
                 clip_norm: Optional[float] = None) -> GradientTransformation:
    from ..optim.base import chain, clip_by_global_norm, scale_by_learning_rate
    from ..optim.adamw import add_decayed_weights
    txs = []
    if clip_norm is not None:
        txs.append(clip_by_global_norm(clip_norm))
    txs += [scale_by_galore(cfg, target_fn, seed),
            add_decayed_weights(weight_decay),
            scale_by_learning_rate(learning_rate)]
    return chain(*txs)


def bucket_by_shape(keys):
    """Group leaf indices by an identical-shape key: ``keys[i]`` is a
    hashable layout descriptor for leaf i (or None to leave it unbucketed).
    Returns ``(buckets, passthrough)`` — a deterministically-ordered list of
    ``(key, [indices])`` plus the unbucketed indices. Leaves sharing a key
    can be stacked and run as one vmapped program (the refresh and 𝒮 bucket
    layout contract: one compiled program per distinct shape, O(buckets)
    ops instead of O(leaves))."""
    groups: dict = {}
    passthrough = []
    for i, key in enumerate(keys):
        if key is None:
            passthrough.append(i)
        else:
            groups.setdefault(key, []).append(i)
    return sorted(groups.items()), passthrough


def _bucketed_manual_refresh(cfg: GaloreConfig, blk_leaves, grads_leaves,
                             refresh_idx, seed):
    """Shape-bucketed round-boundary refresh: blocks with identical
    (basis shape, moment shape) share one stacked bucket whose key
    derivation, basis draw (QR / RSVD / SVD), and r×r moment transfer are
    emitted once and vmapped — O(buckets) ops instead of O(leaves). Per-block
    keys fold the *original* leaf index so every basis is bit-identical to
    the per-leaf reference loop (the broadcast-a-seed protocol is unaffected).
    """
    out = [None] * len(blk_leaves)
    buckets, passthrough = bucket_by_shape(
        [(tuple(st.basis.shape), tuple(st.m.shape))
         if isinstance(st, GaloreBlockState) else None for st in blk_leaves])
    for i in passthrough:
        out[i] = blk_leaves[i]

    for (bshape, mshape), idxs in buckets:
        rank = bshape[-1]
        dim = bshape[-2]
        lead = bshape[:-2]
        side = proj.RIGHT if mshape[-1] == rank else proj.LEFT
        basis = jnp.stack([blk_leaves[i].basis for i in idxs])
        m = jnp.stack([blk_leaves[i].m for i in idxs])
        v = jnp.stack([blk_leaves[i].v for i in idxs])
        block_ids = jnp.asarray(idxs, jnp.uint32)
        keys = jax.vmap(lambda bid: proj.seeded_block_key(
            seed, refresh_idx, bid))(block_ids)
        if lead:
            keys = jax.vmap(lambda kk: proj.stacked_keys(kk, lead[0]))(keys)
        if grads_leaves is not None:
            g32 = jnp.stack([grads_leaves[i] for i in idxs]).astype(
                jnp.float32)
            if cfg.use_exact_svd:
                new_basis = proj.svd_basis_nd(g32, rank, side)
            else:
                new_basis = proj.rsvd_basis_nd(g32, rank, side, keys,
                                               cfg.oversample)
        else:
            new_basis = proj.random_basis_nd(keys, dim, rank)
        m_new = proj.reproject(m, basis, new_basis, side)
        v_new = jnp.maximum(proj.reproject(v, basis, new_basis, side), 0.0)
        for j, i in enumerate(idxs):
            out[i] = GaloreBlockState(basis=new_basis[j], m=m_new[j],
                                      v=v_new[j])
    return out


def _refresh_operands(cfg: GaloreConfig, state: GaloreState, refresh_idx,
                      grads: Optional[PyTree]):
    """The shared head of a round-boundary refresh: the gradient leaves a
    data-driven refresh reads (None for the seeded-random one), the refresh
    index as uint32, and the flattened block-state leaves."""
    grads_leaves = None
    if grads is not None:
        # Data-driven refreshes need a *concrete* refresh index (the round
        # number) — the adaptive-vs-random decision is made at trace time.
        refresh_idx_int = int(refresh_idx)
        adaptive = (cfg.refresh_mode != "random"
                    and refresh_idx_int < cfg.adaptive_steps)
        if adaptive:
            grads_leaves = jax.tree_util.tree_leaves(grads)
    refresh_idx = jnp.asarray(refresh_idx, jnp.uint32)
    blk_leaves, treedef = jax.tree_util.tree_flatten(state.blocks,
                                                     is_leaf=_is_block)
    return grads_leaves, refresh_idx, blk_leaves, treedef


def manual_refresh(cfg: GaloreConfig, state: GaloreState, refresh_idx,
                   grads: Optional[PyTree] = None) -> GaloreState:
    """Refresh every block basis *now* (round-boundary refresh used by the
    federated engine; the in-step ``count % τ`` path is used by the compiled
    production train step).

    Data-driven (RSVD/SVD of ``grads``) when ``grads`` is given and
    ``refresh_idx < adaptive_steps``; seeded-random otherwise. With
    ``grads=None`` (the engine's seeded-broadcast round boundary) the refresh
    index may be a traced value, so the refresh is jit/scan-safe and the
    fused round program can run it with a scanned round counter. Execution
    is shape-bucketed (one vmapped key-derivation + QR + transfer per
    bucket); :func:`manual_refresh_per_leaf` is its reference.
    """
    grads_leaves, refresh_idx, blk_leaves, treedef = _refresh_operands(
        cfg, state, refresh_idx, grads)
    out = _bucketed_manual_refresh(cfg, blk_leaves, grads_leaves,
                                   refresh_idx, state.seed)
    return GaloreState(count=state.count, seed=state.seed,
                       blocks=jax.tree_util.tree_unflatten(treedef, out))


def manual_refresh_per_leaf(cfg: GaloreConfig, state: GaloreState,
                            refresh_idx,
                            grads: Optional[PyTree] = None) -> GaloreState:
    """Reference for :func:`manual_refresh`: the same refresh as a per-leaf
    loop (one key derivation, basis draw and transfer per target block).
    Tests hold the bucketed refresh to it."""
    grads_leaves, refresh_idx, blk_leaves, treedef = _refresh_operands(
        cfg, state, refresh_idx, grads)
    out = []
    for block_id, st in enumerate(blk_leaves):
        if not isinstance(st, GaloreBlockState):
            out.append(st)
            continue
        rank = st.basis.shape[-1]
        # Projected buffers are (rows, r) for right-side blocks and (r, cols)
        # for left-side blocks (Appendix A.1 shape summary).
        side = proj.RIGHT if st.m.shape[-1] == rank else proj.LEFT
        keys = _block_keys(state.seed, refresh_idx, block_id,
                           st.basis.shape[:-2])
        if grads_leaves is not None:
            g32 = grads_leaves[block_id].astype(jnp.float32)
            if cfg.use_exact_svd:
                new_basis = proj.svd_basis_nd(g32, rank, side)
            else:
                new_basis = proj.rsvd_basis_nd(g32, rank, side, keys,
                                               cfg.oversample)
        else:
            new_basis = proj.random_basis_nd(keys, st.basis.shape[-2], rank)
        m = proj.reproject(st.m, st.basis, new_basis, side)
        v = jnp.maximum(proj.reproject(st.v, st.basis, new_basis, side), 0.0)
        out.append(GaloreBlockState(basis=new_basis, m=m, v=v))
    return GaloreState(count=state.count, seed=state.seed,
                       blocks=jax.tree_util.tree_unflatten(treedef, out))


@jax.named_scope("galore.refresh")
def maybe_refresh_instep(cfg: GaloreConfig, state: GaloreState) -> GaloreState:
    """Hoisted in-step refresh for the lift-free local step.

    Fires on the dense path's exact predicate (``count % τ == 0``,
    ``refresh_idx = count // τ``) but *before* the step's forward instead of
    inside the optimizer update — so the delta-aware forward reads (and the
    projected cotangent therefore arrives on) the refreshed basis, which is
    precisely the basis the dense path would project its basis-independent
    dense gradient onto. Equivalent by construction wherever the factored
    client model is valid (refreshes land only where R_i ≡ 0).

    Seeded-random refreshes only (:func:`manual_refresh` with ``grads=None``)
    — callers must not enter the lift-free path when a data-driven refresh
    could fire (``refresh_mode='svd'`` or an in-window adaptive refresh),
    since those need the dense gradient this path never materializes."""
    do = (state.count % cfg.refresh_every) == 0
    idx = state.count // cfg.refresh_every
    return jax.lax.cond(do, lambda s: manual_refresh(cfg, s, idx),
                        lambda s: s, state)


# --------------------------------------------- factored-delta client state --
#
# Within a federated round every GaLoreAdamW local update lives in the shared
# rank-r subspace (the projector refreshes only at local step 0, where the
# round-start delta is identically zero), so a client never needs a dense
# per-client weight copy: its whole trainable state is the factored
# accumulator R_i (shaped like the projected moments) around the broadcast
# global base,
#
#     W_i(t) = base_scale(t) · W_global + lift(R_i(t), B_i),
#     base_scale(t) = (1 - η λ)^t,
#
# with decoupled weight decay absorbed into the scalar ``base_scale`` so the
# delta stays *exactly* rank-r (the dense AdamW recurrence
# W ← (1-ηλ)W - η·lift(ũ) splits leaf-wise into base_scale and R_i because
# the lift is linear). O(r(m+n)) persistent state per client per block
# instead of O(m·n); aggregation closes over ``base_scale·W + Σ wᵢ lift(Rᵢ)``.


def _moment_side(st: GaloreBlockState) -> str:
    """Projected buffers are (rows, r) right / (r, cols) left (Appendix A.1)."""
    return proj.RIGHT if st.m.shape[-1] == st.basis.shape[-1] else proj.LEFT


def all_blocks_projected(state: GaloreState) -> bool:
    """Whether every trainable leaf is a GaLore target block — the
    precondition for the factored-delta client representation (a
    ``DenseMoments`` leaf takes full-rank Adam updates that no rank-r
    accumulator can carry)."""
    leaves = jax.tree_util.tree_leaves(
        state.blocks, is_leaf=lambda x: isinstance(x, (GaloreBlockState,
                                                       DenseMoments)))
    return all(isinstance(s, GaloreBlockState) for s in leaves)


def zero_client_deltas(state: GaloreState) -> PyTree:
    """Round-start factored accumulators R_i = 0, shaped like the projected
    moments (works on concrete states and ``eval_shape`` pytrees alike)."""
    def one(st):
        return jnp.zeros(st.m.shape, jnp.float32)
    return jax.tree_util.tree_map(
        one, state.blocks,
        is_leaf=lambda x: isinstance(x, (GaloreBlockState, DenseMoments)))


def lift_client_trainable(base: PyTree, deltas: PyTree, state: GaloreState,
                          base_scale) -> PyTree:
    """The transient dense weight read ``base_scale·W + lift(R_i, B_i)`` per
    target leaf — the only place a client's dense weights ever materialize
    (inside the local step's forward/backward; never as persistent state)."""
    def one(w0, d, st):
        lifted = proj.project_back(d, st.basis.astype(jnp.float32),
                                   _moment_side(st))
        return (base_scale * w0.astype(jnp.float32) + lifted).astype(w0.dtype)
    return jax.tree_util.tree_map(one, base, deltas, state.blocks)


class LiftFreeGrads(NamedTuple):
    """Lift-free gradient bundle: per-leaf *projected* cotangents (moment
    shape — the delta-aware VJP emits them in rank-r coordinates) plus the
    exact squared dense-gradient norm probes that stand in for the dense
    leaves in global-norm clipping."""
    proj: PyTree    # g̃ per target leaf, shaped like the projected moments
    nsq: PyTree     # ‖dense g‖² per leaf (scalar, or (nb,) for stacked)


def liftfree_params(base: PyTree, deltas: PyTree, nsq: PyTree,
                    state: GaloreState, base_scale) -> PyTree:
    """Build the delta-context trainable tree: each target leaf becomes a
    :class:`models.layers.LowRankDelta` carrying (base W, basis, R̃, norm
    probe, base_scale) — the loss consumes it through ``layers.dense`` /
    ``@`` and neither the lifted weight nor a dense cotangent ever exists.
    ``base_scale`` is broadcast per-layer for stacked (nb, m, n) leaves so
    the node slices cleanly under the model's scan over layers."""
    from ..models.layers import LowRankDelta

    def one(w0, d, ns, st):
        lead = w0.shape[:-2]
        return LowRankDelta(
            w=w0, basis=st.basis.astype(jnp.float32),
            rt=d.astype(jnp.float32), nsq=ns,
            scale=jnp.broadcast_to(jnp.asarray(base_scale, jnp.float32),
                                   lead))
    return jax.tree_util.tree_map(one, base, deltas, nsq, state.blocks)


def liftfree_nsq0(deltas: PyTree) -> PyTree:
    """Zero norm probes, one scalar per target leaf (per layer for stacked
    leaves): the differentiated inputs whose cotangents come back as
    ‖dense g‖² from the delta-aware VJP."""
    return jax.tree_util.tree_map(
        lambda d: jnp.zeros(d.shape[:-2], jnp.float32), deltas)


def liftfree_value_and_grad(loss_of_params, base: PyTree, deltas: PyTree,
                            state: GaloreState, base_scale):
    """``(loss, LiftFreeGrads)`` for one lift-free local step: differentiate
    the loss wrt the rank-r accumulators (cotangents arrive projected) and
    the norm probes (cotangents arrive as exact dense-grad squared norms).
    The base weights, bases, and scale are closed-over constants — AD never
    touches them, so no dense m×n cotangent exists in the program."""
    def wrapped(dl, ns):
        return loss_of_params(liftfree_params(base, dl, ns, state,
                                              base_scale))
    loss, (gt, nsq) = jax.value_and_grad(wrapped, argnums=(0, 1))(
        deltas, liftfree_nsq0(deltas))
    return loss, LiftFreeGrads(proj=gt, nsq=nsq)


@jax.named_scope("galore.update")
def factored_adamw_step(cfg: GaloreConfig, grads, opt_state, deltas,
                        base_scale, *, lr, weight_decay: float = 0.0,
                        clip_norm: Optional[float] = None):
    """One GaLoreAdamW local step in factored-delta coordinates.

    Mirrors the :func:`galore_adamw` chain (global-norm clip →
    ``scale_by_galore`` → decoupled weight decay → lr) with the ambient lift
    eliminated: the preconditioner emits the *projected* ũ
    (``galore_transform_update(project_back=False)``) and the AdamW weight
    recurrence is applied leaf-wise to the factored state,

        R_i ← R_i − η(ũ + λ R_i),   base_scale ← base_scale − η λ base_scale.

    Requires every trainable leaf to be a target block
    (:func:`all_blocks_projected`) and the basis to be fixed whenever any
    R_i ≠ 0 — i.e. projector refreshes may only fire at local step 0, where
    the round-start accumulators are identically zero (``refresh_every %
    local_steps == 0`` in the runtime; the engine refreshes only at round
    boundaries). Returns ``(new_deltas, new_base_scale, new_opt_state)`` with
    the optimizer state structurally identical to the dense chain's (the 𝒮 /
    install / stacking machinery is representation-agnostic). With a schedule
    ``lr`` the step size reads the chain's ``ScaleByLrState`` count, which is
    batched per client — callers must treat ``base_scale`` as per-client
    (vmap out axis 0); the aggregation consumes it as ``Σ wᵢ sᵢ``.

    ``grads`` may be the dense per-leaf gradients (the transient-lift read)
    or a :class:`LiftFreeGrads` bundle (the lift-free read): projected
    cotangents consumed with the ``Pᵀg`` projection skipped, and global-norm
    clipping driven by the exact dense-norm probes — same arithmetic as
    ``clip_by_global_norm`` on gradients that never materialized."""
    from ..optim.base import ScaleByLrState, global_norm
    if isinstance(opt_state, GaloreState):
        states = [opt_state]
    else:
        states = list(opt_state)
    new_states = list(states)
    lift_free = isinstance(grads, LiftFreeGrads)
    if lift_free:
        grads, nsq = grads.proj, grads.nsq
    if clip_norm is not None:
        # Same arithmetic as optim.base.clip_by_global_norm on the dense
        # gradients (the factored path changes the state, not the math).
        if lift_free:
            gnorm = jnp.sqrt(sum(jnp.sum(x)
                                 for x in jax.tree_util.tree_leaves(nsq)))
        else:
            gnorm = global_norm(grads)
        cscale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g * cscale, grads)
    gi = next(i for i, s in enumerate(states) if isinstance(s, GaloreState))
    ut, new_states[gi] = galore_transform_update(cfg, grads, states[gi],
                                                 project_back=False,
                                                 projected=lift_free)
    step_lr = None
    for i, s in enumerate(states):
        if isinstance(s, ScaleByLrState):
            step_lr = lr(s.count) if callable(lr) else lr
            new_states[i] = ScaleByLrState(count=s.count + 1)
    if step_lr is None:
        if callable(lr):
            raise ValueError("a schedule lr needs the chain's ScaleByLrState "
                             "to supply the step count")
        step_lr = lr
    new_deltas = jax.tree_util.tree_map(
        lambda d, u: d - step_lr * (u + weight_decay * d), deltas, ut)
    new_scale = base_scale - step_lr * weight_decay * base_scale
    if isinstance(opt_state, GaloreState):
        return new_deltas, new_scale, new_states[0]
    return new_deltas, new_scale, tuple(new_states)


# ----------------------------------------------- client-axis state layout ---
#
# Stacked client optimizer states keep the per-client moments/bases batched
# along axis 0 but ride the GaLore step counter and round seed UNBATCHED:
# they are identical across clients by construction, and a scalar count keeps
# the in-step `count % τ` refresh a real `lax.cond` under the client vmap
# (a batched predicate lowers to a select that computes the RSVD branch every
# local step). These helpers are the single source of truth for that layout,
# shared by the engine, the sharded runtime, and the dry-run.


def map_opt_layout(opt_state, batched: Callable, scalar: Callable = lambda x: x):
    """Map ``batched`` over the per-client leaves of a (possibly chained)
    optimizer state and ``scalar`` over the unbatched GaLore count/seed."""
    def per_state(s):
        if isinstance(s, GaloreState):
            return GaloreState(count=scalar(s.count), seed=scalar(s.seed),
                               blocks=jax.tree_util.tree_map(batched,
                                                             s.blocks))
        return jax.tree_util.tree_map(batched, s)

    if isinstance(opt_state, GaloreState):
        return per_state(opt_state)
    return tuple(per_state(s) for s in opt_state)


def client_opt_axes(opt_state):
    """The vmap in/out axes tree for a client-stacked optimizer state:
    0 everywhere except the GaLore count/seed, which stay scalar."""
    return map_opt_layout(opt_state, batched=lambda _: 0,
                          scalar=lambda _: None)


def stack_opt_state(opt_state, n_clients: int, copy: bool = False):
    """Broadcast one optimizer state along the client axis in the
    unbatched-count/seed layout. ``copy=True`` materializes real per-client
    buffers (for eagerly-held state that will be donated)."""
    def bcast(x):
        out = jnp.broadcast_to(x, (n_clients,) + x.shape)
        return out.copy() if copy else out
    return map_opt_layout(opt_state, batched=bcast)


def chunk_opt_state(opt_state, n_chunks: int, chunk: int):
    """Reshape a client-stacked state (C, …) into chunk-streamed (n_chunks,
    B, …) form for a ``lax.scan`` over cohort chunks. The unbatched
    count/seed are broadcast along the chunk axis (every chunk starts the
    round from the same scalar state) so they can ride the scan xs."""
    return map_opt_layout(
        opt_state,
        batched=lambda x: x.reshape((n_chunks, chunk) + x.shape[1:]),
        scalar=lambda x: jnp.broadcast_to(x, (n_chunks,) + x.shape))


def unchunk_opt_state(opt_state, n_clients: int):
    """Inverse of :func:`chunk_opt_state` on scan-stacked chunk outputs:
    merge (n_chunks, B, …) back to (C, …); collapse the chunk-replicated
    scalars (identical across chunks — each chunk advances the same
    round-start counter by the same T steps)."""
    return map_opt_layout(
        opt_state,
        batched=lambda x: x.reshape((n_clients,) + x.shape[2:]),
        scalar=lambda x: x[0])


# ------------------------------------------------- fed-layer state access ---

def galore_state_of(opt_state) -> GaloreState:
    """Find the GaloreState inside a chained optimizer state."""
    if isinstance(opt_state, GaloreState):
        return opt_state
    for s in opt_state:
        if isinstance(s, GaloreState):
            return s
    raise ValueError("no GaloreState in optimizer state")


def replace_galore_state(opt_state, new: GaloreState):
    if isinstance(opt_state, GaloreState):
        return new
    return tuple(new if isinstance(s, GaloreState) else s for s in opt_state)


def extract_projected_v(state: GaloreState) -> PyTree:
    """The per-block projected second moments ṽ — the client uplink payload."""
    def pick(st):
        return st.v if isinstance(st, GaloreBlockState) else None
    return jax.tree_util.tree_map(
        pick, state.blocks,
        is_leaf=lambda x: isinstance(x, (GaloreBlockState, DenseMoments)))


def extract_bases(state: GaloreState) -> PyTree:
    def pick(st):
        return st.basis if isinstance(st, GaloreBlockState) else None
    return jax.tree_util.tree_map(
        pick, state.blocks,
        is_leaf=lambda x: isinstance(x, (GaloreBlockState, DenseMoments)))


def with_projected_v(state: GaloreState, new_v: PyTree) -> GaloreState:
    """Install server-synchronized ṽ (next-round initialization, Alg. 1 l.13)."""
    def put(st, nv):
        if isinstance(st, GaloreBlockState) and nv is not None:
            return GaloreBlockState(basis=st.basis, m=st.m,
                                    v=jnp.maximum(nv.astype(jnp.float32), 0.0))
        return st
    blocks = jax.tree_util.tree_map(
        put, state.blocks, new_v,
        is_leaf=lambda x: isinstance(x, (GaloreBlockState, DenseMoments)))
    return GaloreState(count=state.count, seed=state.seed, blocks=blocks)


def with_seed(state: GaloreState, seed) -> GaloreState:
    return GaloreState(count=state.count,
                       seed=jnp.asarray(seed, jnp.uint32), blocks=state.blocks)

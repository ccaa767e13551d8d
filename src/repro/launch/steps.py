"""Step functions lowered by the dry-run / executed by train.py & serve.py.

The train step IS the paper's client workload: one FedGaLore local step —
dense gradients on the target modules, GaLoreAdamW update in the rank-r
subspace, frozen base weights. Clients are vmapped over the (pod, data) mesh
axes; the frozen base is FSDP-sharded (identical across clients, so weight
sharding is sound), while each client's trainable copy shards over the model
axis only.

``make_fed_round_step`` additionally lowers a *whole round*: T local steps
(scan) + FedAvg aggregation (weighted mean over the client axis) + the
server-side state filter 𝒮 (Algorithm 1, line 12) run **inside the mesh** —
factored on the projected ṽ (shared-basis rounds) or via heterogeneous-basis
r×r transfer Grams (``refresh_mode='svd'``, diverged bases), followed by the
synced-state install and seed bump for the next round. The paper's full
𝒯→𝒜→𝒮 pipeline is one SPMD program: the round never drops out of the mesh
onto the host. Passing ``state_sync=None`` lowers the 𝒯→𝒜 program alone
(raw end-of-round states returned): the dry-run's default, and the body of
the runtime's pipelined scan, which runs :func:`sync_client_states` one
round late.

Client memory model of the round program (mirrors ``core.fed``): when every
in-step refresh lands on local step 0 (``refresh_every % local_steps == 0``)
and every trainable leaf is a target block, each client's round state is the
rank-r factored accumulator ``R_i`` around the broadcast global base, and
the local step is **lift-free** (:func:`client_round_liftfree`): target
leaves enter the model as ``models.layers.LowRankDelta`` nodes whose
delta-aware projections compute ``base_scale·(x@W) + split-matmul(R_i)``
directly (``kernels.lowrank_linear`` on TPU) and whose custom VJP returns
the ``R_i`` cotangent already in rank-r coordinates — no ``base_scale·W +
lift(R_i)`` transient, no dense m×n gradient, exact global-norm clipping via
the VJP's dense-norm probes. 𝒜 collapses to ``base_scale·W + Σ wᵢ
lift(Rᵢ)`` with no dense (C, m, n) weight stack anywhere in the program.
``refresh_mode='svd'`` (data-driven refreshes need the dense per-client
gradient) and MLA under blockwise attention take the transient-lift read
(:func:`client_round_factored`) instead. In-step seeded-random refreshes are
hoisted before the forward (``galore.maybe_refresh_instep``) so cotangents
arrive on the refreshed basis. ``client_chunk=B`` streams the cohort through
the round in C/B sequential chunks (a ``lax.scan`` over the chunked client
axis), bounding the dense forward/backward working set by B clients.
Stacked client optimizer states ride the GaLore count/seed UNBATCHED
(``galore.stack_opt_state`` layout) so the in-step ``count % τ`` refresh
stays a real ``lax.cond`` under the client vmap. Rounds whose refreshes do
not land on step 0 keep dense per-client weight stacks.

The dense-per-client round with the dense-lift 𝒮 that tests hold this
program to is ``core.reference.make_dense_round_step``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import aggregation as agg_lib
from ..core import galore as gal
from ..core import projector as proj
from ..core import state_sync as sync_lib
from ..core.fed import merge_dense, split_trainable
from ..models import model as model_lib
from ..optim.base import apply_updates

PyTree = Any


def galore_target_fn(cfg: ArchConfig) -> Callable:
    """The paper's target modules, adapted per family: attention +
    dense-MLP projections; Mamba in/out projections; RWKV6
    time-mix/channel-mix matrices. Experts, routers, embeddings and the
    output head (LM or classifier) frozen: RoBERTa-base trains its 72
    projections (q, k, v, o, up, down x 12), its biases ride frozen."""

    def fn(path: str, leaf) -> bool:
        if leaf.ndim < 2:
            return False
        if "embed" in path or "lm_head" in path:
            return False
        if "/moe/" in path or "/shared/" in path:
            return False
        last = path.split("/")[-1]
        if "/attn/" in path or "/mlp/" in path:
            # Stacked scan-block layout: the projection weights are the 3-D
            # (nb, m, n) leaves (one projector per layer). The 2-D leaves
            # under these prefixes are stacked bias/norm VECTORS (bq/bk/bv,
            # q_a_norm, …) — excluded from the target split, i.e. FROZEN
            # alongside embeddings/routers (the paper's target modules are
            # the projections only).
            return leaf.ndim >= 3
        if "/mamba/" in path:
            return last in ("in_proj", "out_proj")
        if "/tmix/" in path:
            return last in ("wr", "wk", "wv", "wg", "wo")
        if "/cmix/" in path:
            return last in ("wk", "wv", "wr")
        return False

    return fn


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    rank: int = 64
    lr: float = 1e-4
    weight_decay: float = 0.01
    clip_norm: Optional[float] = 1.0
    refresh_every: int = 200
    local_steps: int = 8                # T (round step only)
    seed: int = 0
    refresh_mode: str = "random"        # production steady-state step
    # The GaLore step's kernel route (core.galore module docstring): None
    # auto-selects the fused Pallas kernel on TPU (interpret fallback on CPU
    # when forced True).
    use_pallas: Optional[bool] = None
    # Mesh axes carrying the client dimension. jax.vmap(spmd_axis_name=...)
    # pins every per-client intermediate's leading dim to these axes —
    # without it SPMD replicated the client dim across the data axis
    # (§Perf iteration A measured 16× inflated loss-tensor bytes).
    client_axes: tuple = ("data",)


def make_galore_cfg(spec: TrainSpec) -> gal.GaloreConfig:
    return gal.GaloreConfig(rank=spec.rank, refresh_every=spec.refresh_every,
                            adaptive_steps=0, refresh_mode=spec.refresh_mode,
                            use_pallas=spec.use_pallas)


def make_galore_tx(cfg: ArchConfig, spec: TrainSpec):
    return gal.galore_adamw(make_galore_cfg(spec), spec.lr, spec.weight_decay,
                            target_fn=lambda p, l: True,  # trainable tree is
                            seed=spec.seed,               # already filtered
                            clip_norm=spec.clip_norm)


def init_train_state(key, cfg: ArchConfig, spec: TrainSpec):
    """(trainable, frozen, opt_state) for ONE client."""
    params = model_lib.init_params(key, cfg)
    trainable, frozen = split_trainable(params, galore_target_fn(cfg))
    tx = make_galore_tx(cfg, spec)
    opt_state = tx.init(trainable)
    return trainable, frozen, opt_state


def make_fed_local_step(cfg: ArchConfig, spec: TrainSpec,
                        n_clients: int) -> Callable:
    """One GaLoreAdamW local step for every client in parallel.

    Args (client-stacked leaves marked ×C):
      trainable ×C, frozen (shared), opt_state ×C (``galore.stack_opt_state``
      layout — the GaLore count/seed ride unbatched through the client vmap),
      batch {tokens ×C (c, b, L), labels ×C, embeds? ×C}
    Returns (trainable ×C, opt_state ×C, loss (C,)).
    """
    tx = make_galore_tx(cfg, spec)

    def client_step(trainable, frozen, opt_state, batch):
        def loss_of(tr):
            params = merge_dense(frozen, tr)
            return model_lib.loss_fn(params, cfg, batch)
        loss, grads = jax.value_and_grad(loss_of)(trainable)
        updates, opt_state = tx.update(grads, opt_state, trainable)
        trainable = apply_updates(trainable, updates)
        return trainable, opt_state, loss

    from ..models.layers import batch_axes_override

    def step(trainable, frozen, opt_state, batch):
        axes = gal.client_opt_axes(opt_state)
        with batch_axes_override(()):
            return jax.vmap(client_step, in_axes=(0, None, axes, 0),
                            out_axes=(0, axes, 0),
                            spmd_axis_name=spec.client_axes)(
                trainable, frozen, opt_state, batch)

    return step


def sync_client_states(out_st, w, n_clients: int, state_sync: str,
                       bases_shared: bool,
                       exclude_zero_weights: bool = False,
                       robust_agg: str = "none",
                       robust_trim: float = 0.2,
                       robust_iters: int = 8,
                       robust_tol: float = 1e-6):
    """Server-side 𝒮 + next-round install on client-stacked optimizer states
    (the in-mesh tail of the round program; also usable eagerly).

    Synchronizes each adapted block's projected ṽ — factored on the shared
    seeded basis, or via heterogeneous r×r transfer Grams when client bases
    diverged (``bases_shared=False``) — installs the broadcast result in
    every client slot, and bumps the round seed. No dense ``(C, m, n)`` view
    is built; ``core.reference.sync_client_states_dense`` is the dense-lift
    reference. Shape-identical leaves run as one vmapped program per bucket
    (`state_sync.map_sync_leaves`). ``exclude_zero_weights`` (the
    participation-masked round) additionally drops zero-weight clients from
    the AJIVE joint-basis estimate — without it they only vanish from the
    final weighted mean, not from the unweighted joint-subspace phases.
    ``robust_agg`` is robust 𝒮: the weighted-mean reductions over the
    projected-moment stacks inside the factored sync protocols are swapped
    for the robust estimator (trimmed-mean / geomedian; heterogeneous bases
    are first re-based onto the client-0 basis via the r×r transfer Grams) —
    ``'none'`` lowers exactly the plain program, bitwise.
    """
    g_stack = gal.galore_state_of(out_st)
    if state_sync != "none":
        bases = gal.extract_bases(g_stack)
        v_upload = gal.extract_projected_v(g_stack)
        vs, treedef = jax.tree_util.tree_flatten(v_upload,
                                                 is_leaf=lambda x: x is None)
        bs = jax.tree_util.tree_leaves(bases, is_leaf=lambda x: x is None)

        def leaf_fn(v_stack, b_stack):
            rank = b_stack.shape[-1]
            side = proj.RIGHT if v_stack.shape[-1] == rank else proj.LEFT
            if bases_shared:
                # Factored 𝒮: sync the (C, ., r) uplink directly; the shared
                # seeded basis cancels, so no (C, m, n) lift and no (n, n)
                # projector. Result is the O(dim·r) projected state.
                return jnp.maximum(sync_lib.sync_block_synced_factored(
                    state_sync, v_stack, side, w, rank,
                    exclude_zero_weights=exclude_zero_weights,
                    robust=robust_agg, trim=robust_trim, iters=robust_iters,
                    tol=robust_tol), 0.0)
            # Diverged bases (data-driven refreshes): the lift → 𝒮 →
            # re-project round-trip closes over r×r transfer Grams.
            return jnp.maximum(sync_lib.sync_block_hetero_factored(
                state_sync, v_stack, b_stack, side, w, rank,
                exclude_zero_weights=exclude_zero_weights,
                robust=robust_agg, trim=robust_trim, iters=robust_iters,
                tol=robust_tol), 0.0)

        synced_leaves = sync_lib.map_sync_leaves(leaf_fn, vs, bs)
        # every client slot shares the synced projected state (a broadcast
        # view of the O(dim·r) buffer, not a dense tensor)
        out = [None if s is None else
               jnp.broadcast_to(s[None], (n_clients,) + s.shape)
               for s in synced_leaves]
        synced_tree = jax.tree_util.tree_unflatten(treedef, out)
        g_new = gal.with_projected_v(g_stack, synced_tree)
    else:
        g_new = g_stack
    g_new = gal.GaloreState(
        count=g_new.count, seed=g_new.seed + 1, blocks=g_new.blocks)
    return gal.replace_galore_state(out_st, g_new)


def client_round_factored(cfg: ArchConfig, spec: TrainSpec, deltas, frozen,
                          opt_state, batches, global_trainable):
    """T factored local steps on one client with the transient-lift read:
    every step materializes ``base_scale·W + lift(R_i)`` per target leaf,
    takes the dense gradient, and updates the rank-r accumulator
    (``galore.factored_adamw_step``). Returns (deltas, opt_state, losses
    (T,), base_scale)."""
    gcfg = make_galore_cfg(spec)

    def one(carry, batch):
        dl, scale, st = carry
        tr = gal.lift_client_trainable(global_trainable, dl,
                                       gal.galore_state_of(st), scale)

        def loss_of(t):
            return model_lib.loss_fn(merge_dense(frozen, t), cfg, batch)
        loss, grads = jax.value_and_grad(loss_of)(tr)
        dl, scale, st = gal.factored_adamw_step(
            gcfg, grads, st, dl, scale, lr=spec.lr,
            weight_decay=spec.weight_decay, clip_norm=spec.clip_norm)
        return (dl, scale, st), loss
    (deltas, scale, opt_state), losses = jax.lax.scan(
        one, (deltas, jnp.ones([], jnp.float32), opt_state), batches)
    return deltas, opt_state, losses, scale


def client_round_liftfree(cfg: ArchConfig, spec: TrainSpec, deltas, frozen,
                          opt_state, batches, global_trainable):
    """T lift-free local steps on one client: hoisted seeded-random refresh,
    delta-context forward (LowRankDelta leaves — no per-leaf transient lift),
    projected-cotangent backward, factored AdamW on the LiftFreeGrads bundle
    (projection GEMM skipped, clipping via the norm probes). Same signature
    and results as :func:`client_round_factored`."""
    gcfg = make_galore_cfg(spec)

    def one(carry, batch):
        dl, scale, st = carry
        g0 = gal.maybe_refresh_instep(gcfg, gal.galore_state_of(st))
        st = gal.replace_galore_state(st, g0)

        def loss_of(t):
            return model_lib.loss_fn(merge_dense(frozen, t), cfg, batch)
        loss, grads = gal.liftfree_value_and_grad(
            loss_of, global_trainable, dl, g0, scale)
        dl, scale, st = gal.factored_adamw_step(
            gcfg, grads, st, dl, scale, lr=spec.lr,
            weight_decay=spec.weight_decay, clip_norm=spec.clip_norm)
        return (dl, scale, st), loss
    (deltas, scale, opt_state), losses = jax.lax.scan(
        one, (deltas, jnp.ones([], jnp.float32), opt_state), batches)
    return deltas, opt_state, losses, scale


def make_fed_round_step(cfg: ArchConfig, spec: TrainSpec, n_clients: int,
                        state_sync: Optional[str] = None,
                        client_chunk: Optional[int] = None,
                        exclude_zero_weights: bool = False,
                        robust_agg: str = "none",
                        quarantine: bool = False,
                        quarantine_zmax: float = 6.0,
                        robust_trim: float = 0.2,
                        robust_iters: int = 8,
                        robust_tol: float = 1e-6,
                        return_weights: bool = False) -> Callable:
    """A full federated round (Algorithm 1) as one SPMD program:

      broadcast (implicit: clients start from the shared global base) →
      T local GaLoreAdamW steps (lax.scan), streamed over cohort chunks →
      𝒜: factored ``base_scale·W + Σ wᵢ lift(Rᵢ)`` (or the dense weighted
      mean over the client axis for dense client stacks) →
      𝒮 (when ``state_sync`` is a protocol name): factored sync of the
      projected second moments, install + seed bump — all inside the mesh;
      the returned states are ready for the next round.

    The client memory model is the rank-r factored one whenever in-step
    refreshes land on local step 0 (``refresh_every % local_steps == 0``)
    and every trainable leaf is a target block, with the lift-free local
    phase unless ``refresh_mode='svd'`` or MLA under blockwise attention
    needs the transient-lift read (module docstring); dense client stacks
    otherwise. ``client_chunk=B`` (must divide ``n_clients``, and B must still cover the
    client mesh axes) runs the local phase in C/B sequential chunks.
    ``state_sync=None`` lowers the 𝒯→𝒜 program alone: raw end-of-round
    states are returned with their ṽ upload (the dry-run default). It is
    also the building block of the runtime's pipelined scan
    (`fedsim.runtime.ShardedFederation.run_rounds`), which defers each
    round's `sync_client_states` to the top of the next round's body.
    ``exclude_zero_weights`` lowers the participation-masked round variant:
    the caller feeds pre-masked weights (zero for non-participants — the
    in-program normalization renormalizes over the participants) and 𝒮
    drops the zero-weight clients from the AJIVE joint basis. Kept off by
    default so the unmasked program stays byte-for-byte what it was before
    the participation layer.
    ``quarantine`` / ``robust_agg`` lower the guarded round variant
    (mirroring ``core.fed``): after the local phase, every client's factored
    contribution is screened (non-finite reduction + ``quarantine_zmax`` ×
    weighted-median norm-outlier test, in factored coordinates) and failures
    fold into the zero-weight mask path — renormalized out of 𝒜, sanitized
    stacks, excluded from the AJIVE score Gram; ``robust_agg`` swaps the
    weighted mean in 𝒜 for a robust reduction
    (``aggregation.robust_factored_lift`` — heterogeneous-basis 'svd' rounds
    re-base every client's stack onto the client-0 basis via the r×r
    transfer Grams, so the coordinate-wise modes stay well-defined), and the
    same mode robustifies 𝒮's projected-moment reductions
    (``sync_client_states``). Both require the factored client round.
    All-honest cohorts short-circuit bitwise onto the unguarded math; the
    defaults lower a program byte-for-byte identical to the pre-defense one.

    The returned ``round_step`` additionally accepts an optional trailing
    ``attack`` operand — the engine-parity ``(C,)`` per-client corruption
    multiplier, applied to each client's factored accumulators AND projected
    moments after the local phase, *before* the quarantine screen (exactly
    ``core.fed.FedEngine._apply_guard``'s injection order). ``attack=None``
    (the default) lowers a program with no injection code at all, so honest
    callers are untouched. Injection requires the factored client round.

    ``return_weights`` appends the post-quarantine renormalized effective
    weight vector as a final output — the pipelined-scan building block:
    the runtime's quarantined ``run_rounds`` carries these weights so the
    deferred next-round 𝒮 reduces over the surviving clients only, letting
    the quarantined scan pipeline one round deep like the honest path.
    """
    tx = make_galore_tx(cfg, spec)
    if robust_agg not in agg_lib.ROBUST_MODES:
        raise ValueError(f"robust_agg={robust_agg!r} not in "
                         f"{agg_lib.ROBUST_MODES}")
    guard = quarantine or robust_agg != "none"
    # Factored deltas are exact only while the basis is fixed whenever any
    # R_i ≠ 0, i.e. refreshes only at local step 0 (count ≡ 0 mod τ there).
    factored_ok = spec.refresh_every % spec.local_steps == 0
    # Lift-free needs every in-step refresh to be seeded-random (the hoisted
    # refresh never sees a gradient): 'svd' mode keeps the transient read.
    # MLA with blockwise attention reads kv_b once per chunk, which breaks
    # the clip-norm probe's exactness (per-use ‖·‖² sum misses cross-chunk
    # terms — models.layers.lowrank_apply): keep the transient read there.
    multi_read = (cfg.attn_chunk and any(
        mix == "mla" for mix, _ in cfg.layer_kinds()))
    liftfree_ok = spec.refresh_mode != "svd" and not multi_read
    chunk = client_chunk or n_clients
    if n_clients % chunk:
        raise ValueError(f"client_chunk={chunk} must divide n_clients="
                         f"{n_clients}")
    n_chunks = n_clients // chunk

    def client_round(trainable, frozen, opt_state, batches):
        def one(carry, batch):
            tr, st = carry
            def loss_of(t):
                return model_lib.loss_fn(merge_dense(frozen, t), cfg, batch)
            loss, grads = jax.value_and_grad(loss_of)(tr)
            updates, st = tx.update(grads, st, tr)
            return (apply_updates(tr, updates), st), loss
        (trainable, opt_state), losses = jax.lax.scan(
            one, (trainable, opt_state), batches)
        return trainable, opt_state, losses

    from ..models.layers import batch_axes_override

    def _stream(local_fn, opt_states, batches):
        """Run the B-client local phase over the cohort: directly for a
        single chunk, as a ``lax.scan`` over C/B (opt_chunk, batch_chunk)
        slices otherwise, reassembling the full (C, …) stacks."""
        if n_chunks == 1:
            return local_fn(opt_states, batches)
        opt_c = gal.chunk_opt_state(opt_states, n_chunks, chunk)
        cb = jax.tree_util.tree_map(
            lambda x: x.reshape((n_chunks, chunk) + x.shape[1:]), batches)
        _, out = jax.lax.scan(
            lambda carry, xs: (carry, local_fn(*xs)), None, (opt_c, cb))
        unchunk = lambda x: x.reshape((n_clients,) + x.shape[2:])
        merged = (jax.tree_util.tree_map(unchunk, out[0]),
                  gal.unchunk_opt_state(out[1], n_clients), unchunk(out[2]))
        if len(out) == 4:                         # factored: (C,) base scales
            merged += (out[3].reshape((n_clients,)),)
        return merged

    def _local_phase_factored(global_trainable, frozen, opt_states, batches,
                              axes):
        """Chunk-streamed factored local phase: (C,…) states/batches →
        (C,…) factored deltas + end-of-round states + losses + per-client
        base scales."""
        g_blocks = gal.galore_state_of(opt_states).blocks
        deltas0 = jax.tree_util.tree_map(
            lambda st: jnp.zeros((chunk,) + st.m.shape[1:], jnp.float32),
            g_blocks,
            is_leaf=lambda x: isinstance(x, (gal.GaloreBlockState,
                                             gal.DenseMoments)))

        client_fn = functools.partial(
            client_round_liftfree if liftfree_ok else client_round_factored,
            cfg, spec)

        def local_fn(opt_chunk, batch_chunk):
            with batch_axes_override(()):
                return jax.vmap(
                    client_fn, in_axes=(0, None, axes, 0, None),
                    out_axes=(0, axes, 0, 0),
                    spmd_axis_name=spec.client_axes)(
                    deltas0, frozen, opt_chunk, batch_chunk,
                    global_trainable)

        return _stream(local_fn, opt_states, batches)

    def _local_phase_dense(global_trainable, frozen, opt_states, batches,
                           axes):
        """Chunk-streamed dense local phase (per-client weight stacks)."""
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (chunk,) + x.shape),
            global_trainable)

        def local_fn(opt_chunk, batch_chunk):
            with batch_axes_override(()):
                return jax.vmap(
                    client_round, in_axes=(0, None, axes, 0),
                    out_axes=(0, axes, 0),
                    spmd_axis_name=spec.client_axes)(
                    stacked, frozen, opt_chunk, batch_chunk)

        return _stream(local_fn, opt_states, batches)

    def round_step(global_trainable, frozen, opt_states, batches, weights,
                   attack=None):
        w = weights / jnp.sum(weights)
        axes = gal.client_opt_axes(opt_states)
        use_factored = (factored_ok and gal.all_blocks_projected(
            gal.galore_state_of(opt_states)))
        if attack is not None and not use_factored:
            raise ValueError("the attack operand requires the factored "
                             "client round")
        if use_factored:
            out_d, out_st, losses, base_scales = _local_phase_factored(
                global_trainable, frozen, opt_states, batches, axes)
            if attack is not None:
                # Adversary injection (engine parity): multiply each
                # client's uplink — factored accumulators AND projected
                # moments — by its attack entry, before the screen.
                tmap = jax.tree_util.tree_map
                ab = lambda x: attack.astype(jnp.float32).reshape(
                    (-1,) + (1,) * (x.ndim - 1))
                out_d = tmap(lambda x: (x.astype(jnp.float32)
                                        * ab(x)).astype(x.dtype), out_d)
                g_st = gal.galore_state_of(out_st)
                v_atk = tmap(
                    lambda x: None if x is None
                    else (x.astype(jnp.float32) * ab(x)).astype(x.dtype),
                    gal.extract_projected_v(g_st),
                    is_leaf=lambda x: x is None)
                out_st = gal.replace_galore_state(
                    out_st, gal.with_projected_v(g_st, v_atk))
            if guard and quarantine:
                # In-round quarantine: screen the factored uplink, fold
                # failures into the zero-weight mask path (sanitized
                # stacks, renormalized weights, moments zeroed out of the
                # score Gram). All-pass verdicts leave every operand
                # bitwise untouched.
                g_st = gal.galore_state_of(out_st)
                v_tree = gal.extract_projected_v(g_st)
                keep = agg_lib.screen_factored_clients(
                    out_d, v_tree, base_scales, w, zmax=quarantine_zmax)
                out_d = agg_lib.mask_client_rows(out_d, keep)
                v_tree = agg_lib.mask_client_rows(v_tree, keep)
                base_scales = jnp.where(keep, base_scales, 1.0)
                w = agg_lib.quarantine_weights(w, keep)
                out_st = gal.replace_galore_state(
                    out_st, gal.with_projected_v(g_st, v_tree))
            # 𝒜 factored: reduce in projected coordinates (shared seeded
            # basis) or contract per-client lifts ('svd' diverges bases).
            bases = gal.extract_bases(gal.galore_state_of(out_st))
            hetero = spec.refresh_mode == "svd"
            sbar = jnp.einsum("c,c->", w, base_scales.astype(jnp.float32))

            def one(x, d_stack, b_stack):
                side = (proj.RIGHT if d_stack.shape[-1] == b_stack.shape[-1]
                        else proj.LEFT)
                lifted = agg_lib.robust_factored_lift(
                    d_stack, b_stack, side, w, robust_agg, hetero=hetero,
                    trim=robust_trim, iters=robust_iters, tol=robust_tol)
                return (sbar * x.astype(jnp.float32)
                        + lifted).astype(x.dtype)

            new_global = jax.tree_util.tree_map(one, global_trainable,
                                                out_d, bases)
        else:
            if guard:
                raise ValueError(
                    "quarantine/robust_agg require the factored client "
                    "round (step-0-aligned refreshes and all-target "
                    "trainables)")
            out_tr, out_st, losses = _local_phase_dense(
                global_trainable, frozen, opt_states, batches, axes)
            # 𝒜: weighted average over the client axis -> all-reduce on mesh
            new_global = jax.tree_util.tree_map(
                lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=(0, 0)
                                        ).astype(x.dtype), out_tr)
        if state_sync is not None:
            # 𝒮 in-mesh: the round program returns next-round-ready states;
            # the pre-sync ṽ is consumed internally, never materialized as
            # an output. A quarantine-guarded round excludes zero-weight
            # clients from the joint basis even when the caller didn't ask
            # for the masked variant (exact no-op on all-positive weights).
            out_st = sync_client_states(
                out_st, w, n_clients, state_sync,
                bases_shared=(spec.refresh_mode != "svd"),
                exclude_zero_weights=exclude_zero_weights or quarantine,
                robust_agg=robust_agg,
                robust_trim=robust_trim, robust_iters=robust_iters,
                robust_tol=robust_tol)
            if return_weights:
                return new_global, out_st, losses, None, w
            return new_global, out_st, losses, None
        # 𝒮 payload for the host-side filter: projected second moments ṽ
        # (client-stacked, O(n·r))
        v_upload = gal.extract_projected_v(gal.galore_state_of(out_st))
        if return_weights:
            return new_global, out_st, losses, v_upload, w
        return new_global, out_st, losses, v_upload

    return round_step


def make_prefill_step(cfg: ArchConfig, cache_len: int) -> Callable:
    def prefill_step(params, tokens, embeds=None):
        state = model_lib.init_decode_state(cfg, tokens.shape[0], cache_len)
        return model_lib.prefill(params, cfg, tokens, state, embeds)
    return prefill_step


def make_decode_step(cfg: ArchConfig) -> Callable:
    def decode(params, token, state):
        return model_lib.decode_step(params, cfg, token, state)
    return decode

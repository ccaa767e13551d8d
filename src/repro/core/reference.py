"""Reference forms of the federated round, for tests to hold the round
programs to.

Nothing under ``repro`` imports this module: the round engines have one
production path each, and these plain functions are what the tests compare
it with, stage by stage. They compute each stage the direct way — one
dispatch per stage, dense per-client weights, 𝒮 on the dense per-client lift
— where the production programs fuse the round into one donated program,
keep rank-r factored client state, read it lift-free, and synchronize in
projected coordinates.

- :func:`dense_sync_block` — 𝒮 of one block by the dense lift: each client's
  ṽ lifted with its own basis, the protocol on the (C, m, n) views, the
  result re-projected onto client 0's basis. Correct for shared and
  diverged bases alike.
- :func:`sync_states_dense` — :func:`dense_sync_block` over an engine's
  client-stacked optimizer states (the synced ṽ tree the engine installs).
- :func:`run_round_eager` — one ``core.fed.FedEngine`` round as separately
  dispatched stages: InitState, the dense vmapped local phase, 𝒜, and
  :func:`sync_states_dense`.
- :func:`sync_client_states_dense` — ``launch.steps.sync_client_states`` by
  the dense lift: 𝒮, install in every client slot, seed bump.
- :func:`make_dense_round_step` — the sharded runtime's round with dense
  per-client weights (``launch.steps.make_fed_local_step`` scanned over the
  T local steps), the weighted mean as 𝒜, and
  :func:`sync_client_states_dense` as 𝒮.

The per-leaf references of the bucketed optimizer step, refresh and 𝒮 live
beside their production code: ``galore.galore_transform_update_per_leaf``,
``galore.manual_refresh_per_leaf`` and ``state_sync.map_sync_leaves_per_leaf``.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import galore as gal
from . import projector as proj
from . import state_sync as sync_lib

PyTree = Any


def dense_sync_block(protocol: str, v_stack, b_stack, w, rank: int,
                     side: str):
    """𝒮 of one adapted block by the dense per-client lift: v_stack
    (C, m, r) | (C, r, n) lifted with each client's own basis b_stack
    (C, dim, r), ``protocol`` run on the (C, m, n) views, the result
    re-projected onto client 0's basis. Stacked scan blocks (C, nb, ·, r)
    map over the layer axis."""
    def sync_one(v_cl, b_cl):
        v32 = v_cl.astype(jnp.float32)
        b32 = b_cl.astype(jnp.float32)
        if side == proj.RIGHT:
            views = jnp.einsum("kmr,knr->kmn", v32, b32)
        else:
            views = jnp.einsum("kmr,krn->kmn", b32, v32)
        lifted = sync_lib.sync_lifted_views(protocol, views, w, rank)
        return sync_lib.project_state(lifted, b_cl[0], side)

    if v_stack.ndim == 4:
        return jax.vmap(sync_one, in_axes=(1, 1))(v_stack, b_stack)
    return sync_one(v_stack, b_stack)


def _synced_tree(protocol: str, stacked_opt_states, w,
                 post: Callable = lambda x: x):
    """:func:`dense_sync_block` over every adapted leaf of a client-stacked
    optimizer state; ``None`` for leaves without a projected ṽ."""
    g_stack = gal.galore_state_of(stacked_opt_states)
    v_tree = gal.extract_projected_v(g_stack)
    vs, treedef = jax.tree_util.tree_flatten(v_tree,
                                             is_leaf=lambda x: x is None)
    bs = jax.tree_util.tree_leaves(gal.extract_bases(g_stack),
                                   is_leaf=lambda x: x is None)

    def leaf_fn(v_stack, b_stack):
        rank = b_stack.shape[-1]
        side = proj.RIGHT if v_stack.shape[-1] == rank else proj.LEFT
        return post(dense_sync_block(protocol, v_stack, b_stack, w, rank,
                                     side))

    synced = sync_lib.map_sync_leaves_per_leaf(leaf_fn, vs, bs)
    return jax.tree_util.tree_unflatten(treedef, synced)


def sync_states_dense(engine, stacked_opt_states, w) -> Optional[PyTree]:
    """The engine's 𝒮 by the dense lift: the synced projected-ṽ tree
    ``FedEngine`` installs at the next InitState, or None when the method
    does not synchronize."""
    if not engine._method_syncs():
        return None
    return _synced_tree(engine.spec.state_sync, stacked_opt_states, w)


def _engine_local_train(engine, trainable, opt_states, batches, frozen):
    return jax.vmap(engine._local_train_one,
                    in_axes=(0, engine._opt_axes, 0, None),
                    out_axes=(0, engine._opt_axes, 0))(
        trainable, opt_states, batches, frozen)


_local_train_jit = jax.jit(_engine_local_train, static_argnums=0)


def run_round_eager(engine, client_batches: PyTree, weights=None):
    """One round of ``engine`` as separately dispatched stages, mutating
    the engine's global state as ``FedEngine.run_round`` does: the
    round-start InitState broadcast to every client, the dense vmapped local
    phase (``FedEngine._local_train_one``, dense per-client weights for
    every method), 𝒜 (``FedEngine._aggregate_pure``), and
    :func:`sync_states_dense`. Returns the ``run_round`` metrics."""
    k_clients = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
    w = engine._normalize_weights(weights, k_clients)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (k_clients,) + x.shape),
        engine.global_trainable)
    st0 = engine._init_state0(engine.round_idx, engine.synced_v,
                              engine.global_trainable)
    opt_states = engine._stack_opt_state(st0, k_clients)
    out_tr, out_opt, losses = _local_train_jit(
        engine, stacked, opt_states, client_batches, engine.frozen)
    engine.global_trainable, engine.frozen = engine._aggregate_pure(
        out_tr, w, engine.frozen, engine.round_idx)
    engine.synced_v = sync_states_dense(engine, out_opt, w)
    engine.round_idx += 1
    return {"local_loss": losses,                          # (K, T)
            "mean_final_loss": float(jnp.mean(losses[:, -1]))}


def sync_client_states_dense(out_st, w, n_clients: int, state_sync: str):
    """``launch.steps.sync_client_states`` by the dense lift: every adapted
    block's ṽ synchronized with :func:`dense_sync_block` (clamped at zero),
    installed in every client slot, and the round seed bumped."""
    g_stack = gal.galore_state_of(out_st)
    if state_sync != "none":
        synced = _synced_tree(state_sync, out_st, w,
                              post=lambda x: jnp.maximum(x, 0.0))
        synced = jax.tree_util.tree_map(
            lambda s: None if s is None
            else jnp.broadcast_to(s[None], (n_clients,) + s.shape),
            synced, is_leaf=lambda x: x is None)
        g_stack = gal.with_projected_v(g_stack, synced)
    g_new = gal.GaloreState(count=g_stack.count, seed=g_stack.seed + 1,
                            blocks=g_stack.blocks)
    return gal.replace_galore_state(out_st, g_new)


def make_dense_round_step(cfg, spec, n_clients: int, state_sync: str):
    """The sharded runtime's round with dense per-client weights:
    ``round_step(global_trainable, frozen, opt_states, batches, weights) ->
    (new_global, next_round_states, losses (C, T))``. The local phase is
    ``launch.steps.make_fed_local_step`` (every client a dense copy of the
    global trainable, dense gradients into the GaLoreAdamW chain) scanned
    over the T steps, 𝒜 the weighted mean over the client axis, 𝒮
    :func:`sync_client_states_dense`. Call it under the runtime's mesh."""
    from ..launch import steps as steps_lib

    local_step = steps_lib.make_fed_local_step(cfg, spec, n_clients)

    def round_step(global_trainable, frozen, opt_states, batches, weights):
        w = weights / jnp.sum(weights)
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape),
            global_trainable)

        def one(carry, batch):
            tr, st = carry
            tr, st, loss = local_step(tr, frozen, st, batch)
            return (tr, st), loss

        per_step = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1),
                                          batches)
        (out_tr, out_st), losses = jax.lax.scan(
            one, (stacked, opt_states), per_step)
        new_global = jax.tree_util.tree_map(
            lambda x: jnp.tensordot(w, x.astype(jnp.float32),
                                    axes=(0, 0)).astype(x.dtype), out_tr)
        out_st = sync_client_states_dense(out_st, w, n_clients, state_sync)
        return new_global, out_st, losses.T

    return round_step

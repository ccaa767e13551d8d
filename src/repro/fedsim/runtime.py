"""Sharded federated runtime: the paper's round as an SPMD program.

Clients live on the (pod, data) mesh axes; each client's trainable copy is
tensor-parallel over the model axis; the frozen base is FSDP-sharded
(identical across clients). One `round_step` call runs the **whole round**
inside the mesh: T local GaLoreAdamW steps per client (lax.scan), factored
aggregation over the client axes, and the server-side state filter 𝒮
(Algorithm 1, line 12) — factored sync of the projected second moments,
broadcast-free O(dim·r) install, seed bump. The round program never drops
out of the mesh onto the host, and the jitted call donates the stacked
client buffers (global trainable + per-client optimizer states), so each
round's outputs reuse the previous round's memory.

Client memory model (``launch.steps`` module docstring): when every in-step
refresh lands on local step 0 (``refresh_every % local_steps == 0``) a
client's round state is the rank-r factored accumulator ``R_i`` around the
shared global base, and the local step is **lift-free**: target leaves flow
into the model as delta-context nodes (``models.layers.LowRankDelta``) whose
split-matmul apply and projected-cotangent VJP replace both the per-leaf
``base_scale·W + lift(R_i)`` transient and the dense m×n gradient
(``refresh_mode='svd'`` takes the transient-lift read — data-driven
refreshes need dense gradients). Decoupled weight decay rides the scalar
``base_scale`` and 𝒜 collapses to ``base_scale·W + Σ wᵢ lift(Rᵢ)``, so no
dense ``(C, m, n)`` per-client weight stack exists anywhere in the round
program; per-client persistent state is O(r(m+n)) per block (the projected
moments + basis). ``client_chunk=B`` additionally streams the cohort
through the round in C/B sequential chunks, bounding the dense
forward/backward working set by B clients and decoupling cohort size from
peak memory (C≈512 rounds on a single host). The stacked optimizer states
ride the GaLore count/seed unbatched (``galore.stack_opt_state``), keeping
the in-step refresh predicate scalar under the client vmap. Refresh
schedules that could strand a non-zero accumulator on a stale basis keep
dense per-client weight stacks.

The server sync runs **factored**: the uplinked ṽ are synchronized directly
in projected coordinates (`state_sync.sync_block_synced_factored` on the
shared seeded basis; `state_sync.sync_block_hetero_factored` via r×r
transfer Grams when data-driven refreshes diverge the bases, e.g.
``refresh_mode='svd'``) — no ``(C, m, n)`` lifted view, ``(n, n)`` joint
projector, or dense per-client broadcast is ever materialized. The round
with dense per-client weights and the dense-lift 𝒮 that tests hold this
runtime to is ``core.reference.make_dense_round_step``.

:meth:`ShardedFederation.run_rounds` drives K rounds as a single
``lax.scan`` dispatch for benchmark sweeps. When the method syncs, the scan
runs the **one-round-deep pipelined schedule**: the body defers round k's 𝒮
to the top of round k+1's iteration (a raw ``state_sync=None`` round core
returns the unsynced states, which ride the carry), and a post-scan drain
runs the final round's 𝒮 so the returned states match K successive
:meth:`ShardedFederation.run_round` calls state-for-state. This is a pure
re-association of the same round math — round k+1's first local update
still consumes round-k *synced* moments — but it lets XLA overlap the r×r
sync chain with round k+1's independent gradient work instead of
serializing 𝒮 between rounds. Quarantined scans pipeline too: the raw round
core returns its post-screen effective weights (``return_weights``), which
ride the scan carry so the deferred 𝒮 reduces over exactly the clients the
quarantine kept.

This is the production counterpart of core.fed.FedEngine (which vmaps
clients on a single host).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core import galore as gal
from ..core import population as pop_lib
from ..launch import steps as steps_lib
from ..sharding import rules as rules_lib

PyTree = Any


class ShardedFederation:
    """``participation`` (a ``core.population.ParticipationConfig``) enables
    the planet-scale participation layer: :meth:`sample_round_mask` draws the
    seeded per-round fault plan, and :meth:`run_round` / :meth:`run_rounds`
    accept per-round participation masks. Masked rounds run a SEPARATELY
    compiled program — same round math on mask-zeroed weights (the
    in-program normalization renormalizes over the participants) plus AJIVE
    joint-basis exclusion of the masked-out clients — so the unmasked
    program stays byte-for-byte what it was before the participation layer,
    and an all-true mask short-circuits onto it (bit-identical by
    construction)."""

    def __init__(self, cfg: ArchConfig, spec: steps_lib.TrainSpec, mesh,
                 n_clients: int, state_sync: str = "ajive", seed: int = 0,
                 client_chunk: Optional[int] = None,
                 participation: Optional[
                     pop_lib.ParticipationConfig] = None,
                 robust_agg: str = "none", quarantine: bool = False,
                 quarantine_zmax: float = 6.0, robust_trim: float = 0.2,
                 robust_iters: int = 8, robust_tol: float = 1e-6):
        self.cfg = cfg
        self.spec = spec
        self.mesh = rules_lib.auto_axes(mesh)
        self.n_clients = n_clients
        self.state_sync = state_sync
        self.participation = participation
        self.quarantine = quarantine
        self.round_idx = 0

        if client_chunk is not None:
            # Chunks sequentialize the client dim, but each chunk's vmap
            # still maps clients onto the mesh — B must cover the client
            # axes or SPMD lowering fails with an opaque sharding error.
            client_devices = 1
            for a in spec.client_axes:
                if a in mesh.shape:
                    client_devices *= mesh.shape[a]
            if client_chunk % client_devices:
                raise ValueError(
                    f"client_chunk={client_chunk} must be a multiple of the "
                    f"client mesh axes size {client_devices} "
                    f"(axes {spec.client_axes})")

        key = jax.random.PRNGKey(seed)
        self.global_trainable, self.frozen, opt_state = \
            steps_lib.init_train_state(key, cfg, spec)
        # Per-client moments/bases batched on axis 0; GaLore count/seed
        # unbatched (identical across clients — scalar keeps the in-step
        # refresh a real cond under the client vmap).
        self.opt_states = gal.stack_opt_state(opt_state, n_clients,
                                              copy=True)
        # 𝒮 + install + seed bump lower inside the round program; the
        # stacked buffers are donated so round k+1's outputs reuse round k's
        # memory.
        # Defense knobs lower INSIDE the round program (steps.
        # make_fed_round_step): quarantine screens the factored uplink and
        # folds failures into the zero-weight mask path; robust_agg swaps
        # the weighted means of 𝒜 AND 𝒮 for robust factored reductions
        # (heterogeneous bases re-based onto client 0 via transfer Grams).
        # Defaults lower the pre-defense program unchanged. The engine-
        # parity (C,) attack-injection operand rides run_round(attack=) —
        # the guarded (exclusion-aware) program applies it to each client's
        # uplink before the screen.
        self._step_kwargs = dict(
            client_chunk=client_chunk,
            robust_agg=robust_agg, quarantine=quarantine,
            quarantine_zmax=quarantine_zmax, robust_trim=robust_trim,
            robust_iters=robust_iters, robust_tol=robust_tol)
        self._robust_sync_kwargs = dict(
            robust_agg=robust_agg, robust_trim=robust_trim,
            robust_iters=robust_iters, robust_tol=robust_tol)
        self._round_core = steps_lib.make_fed_round_step(
            cfg, spec, n_clients, state_sync=state_sync,
            **self._step_kwargs)
        self._round = jax.jit(self._round_core, donate_argnums=(0, 2))
        self._rounds_scan = None
        # Participation-masked variants (built lazily — a federation that
        # never sees a partial mask never compiles them).
        self._round_masked_core = None
        self._round_masked = None
        self._rounds_scan_masked = None
        # Raw (state_sync=None) round core for the pipelined scans: the
        # body defers 𝒮 into the next iteration, so the scanned round must
        # return unsynced states (built lazily).
        self._round_core_raw = None

    # -------------------------------------------------- participation -------
    def sample_round_mask(self, round_idx: Optional[int] = None) -> np.ndarray:
        """The seeded on-time participation mask for ``round_idx`` (default:
        the next round) under this federation's ``participation`` config — a
        pure host function of (config, round), reproducible across per-round
        and scanned drivers and across restarts."""
        if self.participation is None:
            return np.ones(self.n_clients, bool)
        r = self.round_idx if round_idx is None else int(round_idx)
        return pop_lib.sample_cohort(self.participation, self.n_clients, r,
                                     self.n_clients).mask

    def _canon_mask(self, mask):
        if mask is None:
            return None
        m = np.asarray(mask, bool).reshape(-1)
        if m.shape != (self.n_clients,):
            raise ValueError(f"mask shape {m.shape} != cohort "
                             f"({self.n_clients},)")
        if not m.any():
            raise ValueError("participation mask drops every client — a "
                             "round needs >= 1 on-time participant")
        return None if m.all() else m

    def _canon_attack(self, attack):
        """Canonicalize a (C,) per-client corruption-multiplier operand.
        An all-ones vector IS the honest round — short-circuit to None so
        the unmasked program runs, bit-identical to no attack at all. (A
        NaN entry never compares equal to 1, so corrupted vectors always
        reach the guarded program.)"""
        if attack is None:
            return None
        a = np.asarray(attack, np.float32).reshape(-1)
        if a.shape != (self.n_clients,):
            raise ValueError(f"attack shape {a.shape} != cohort "
                             f"({self.n_clients},)")
        return None if bool(np.all(a == 1.0)) else jnp.asarray(a)

    def _masked_round(self):
        if self._round_masked is None:
            self._round_masked_core = steps_lib.make_fed_round_step(
                self.cfg, self.spec, self.n_clients,
                state_sync=self.state_sync,
                exclude_zero_weights=True, **self._step_kwargs)
            self._round_masked = jax.jit(self._round_masked_core,
                                         donate_argnums=(0, 2))
        return self._round_masked

    def _base_weights(self, weights):
        return (jnp.full((self.n_clients,), 1.0 / self.n_clients)
                if weights is None else weights)

    def run_round(self, batches: PyTree,
                  weights: Optional[jnp.ndarray] = None, mask=None,
                  attack=None):
        """batches: pytree with leading (C, T, b, ...) axes.

        ``mask`` (optional bool (C,)) marks the round's on-time
        participants: masked-out clients keep their compiled slot but get
        zero effective weight (the in-program normalization renormalizes
        over the participants) and are excluded from the AJIVE joint basis.
        An all-true mask short-circuits onto the unmasked program —
        bit-identical to calling without a mask.

        ``attack`` (optional (C,) float) is the engine-parity per-client
        corruption multiplier (``core.fed.FedEngine.run_round(attack=)``):
        each client's factored uplink — accumulators and projected moments —
        is multiplied by its entry after the local phase, before the
        quarantine screen, inside the SPMD round program. Attacked rounds
        run the exclusion-aware guarded program (zero-weight clients leave
        the AJIVE joint basis — an exact no-op on all-positive weights,
        matching the engine's guarded jit); an all-ones attack
        short-circuits onto the honest program, bit-identical to no attack.
        Requires the factored client round."""
        mask = self._canon_mask(mask)
        attack = self._canon_attack(attack)
        w = self._base_weights(weights)
        if mask is None and attack is None:
            round_fn = self._round
        else:
            round_fn = self._masked_round()
            if mask is not None:
                w = w * jnp.asarray(mask, w.dtype)
        extra = () if attack is None else (attack,)
        with jax.set_mesh(self.mesh):
            # 𝒮 runs in-mesh; the returned states are next-round-ready.
            self.global_trainable, self.opt_states, losses, _ = round_fn(
                self.global_trainable, self.frozen, self.opt_states,
                batches, w, *extra)
        self.round_idx += 1
        return {"losses": losses,
                "mean_final_loss": float(jnp.mean(losses[:, -1]))}

    def run_rounds(self, batches: PyTree,
                   weights: Optional[jnp.ndarray] = None, masks=None):
        """K rounds as ONE dispatch: ``lax.scan`` over the in-mesh round.

        batches: pytree with leading (K rounds, C, T, b, ...) axes.

        ``masks`` (optional bool (K, C)) applies per-round participation
        masks: the per-round mask-zeroed weights ride the scan as xs and the
        scanned body is the exclusion-aware masked round. All-true masks
        short-circuit onto the unmasked scan program.

        When :meth:`_pipeline_rounds` holds, the scan is the one-round-deep
        pipelined schedule (see the module docstring): each body syncs the
        *previous* round's states before its local phase and a post-scan
        drain syncs the last round, so results are state-for-state those of
        K :meth:`run_round` calls while 𝒮 overlaps the next round's gradient
        work.
        """
        leading = jax.tree_util.tree_leaves(batches)[0].shape
        k_rounds = leading[0]
        w = self._base_weights(weights)
        if masks is not None:
            masks = np.asarray(masks, bool)
            if masks.shape != (int(k_rounds), int(self.n_clients)):
                raise ValueError(f"masks shape {masks.shape} != "
                                 f"({k_rounds}, {self.n_clients})")
            if not masks.any(axis=1).all():
                raise ValueError("a round's participation mask drops every "
                                 "client")
            if masks.all():
                masks = None
        pipelined = self._pipeline_rounds()
        if masks is None:
            if self._rounds_scan is None:
                if pipelined:
                    self._raw_round()    # builds _round_core_raw
                    quar = self.quarantine

                    def scan_rounds(global_trainable, frozen, opt_states,
                                    bat, w):
                        sync = self._make_scan_sync(quar)
                        if quar:
                            # Quarantined rounds rewrite the effective
                            # weights inside the round; the raw core
                            # returns them (return_weights) and they ride
                            # the carry so the deferred 𝒮 reduces over the
                            # survivors only — this is what lets the
                            # quarantined scan pipeline one round deep
                            # like the honest path.
                            def body(carry, round_b):
                                g_tr, states, first, w_prev = carry
                                states = jax.lax.cond(
                                    first, lambda s: s,
                                    lambda s: sync(s, w_prev), states)
                                g_tr, states, losses, _, w_eff = \
                                    self._round_core_raw(
                                        g_tr, frozen, states, round_b, w)
                                return (g_tr, states, jnp.zeros((), bool),
                                        w_eff), losses
                            (g_tr, states, _, w_last), losses = jax.lax.scan(
                                body, (global_trainable, opt_states,
                                       jnp.ones((), bool), w), bat)
                            return (g_tr, sync(states, w_last)), losses

                        def body(carry, round_b):
                            g_tr, states, first = carry
                            states = jax.lax.cond(
                                first, lambda s: s, lambda s: sync(s, w),
                                states)
                            g_tr, states, losses, _ = self._round_core_raw(
                                g_tr, frozen, states, round_b, w)
                            return (g_tr, states,
                                    jnp.zeros((), bool)), losses
                        (g_tr, states, _), losses = jax.lax.scan(
                            body, (global_trainable, opt_states,
                                   jnp.ones((), bool)), bat)
                        # Pipeline drain: the last round's 𝒮 never ran in a
                        # body — run it here so the returned states match
                        # the sequential schedule state-for-state.
                        return (g_tr, sync(states, w)), losses
                else:
                    def scan_rounds(global_trainable, frozen, opt_states,
                                    bat, w):
                        def body(carry, round_b):
                            g_tr, states = carry
                            g_tr, states, losses, _ = self._round_core(
                                g_tr, frozen, states, round_b, w)
                            return (g_tr, states), losses
                        return jax.lax.scan(
                            body, (global_trainable, opt_states), bat)
                self._rounds_scan = jax.jit(scan_rounds,
                                            donate_argnums=(0, 2))
            scan_fn, w_arg = self._rounds_scan, w
        else:
            self._masked_round()     # builds _round_masked_core
            if self._rounds_scan_masked is None:
                if pipelined:
                    self._raw_round()    # builds _round_core_raw
                    quar = self.quarantine

                    def scan_rounds_masked(global_trainable, frozen,
                                           opt_states, bat, w_rounds):
                        sync = self._make_scan_sync(True)

                        def body(carry, xs):
                            round_b, w_r = xs
                            g_tr, states, first, w_prev = carry
                            # 𝒮 of the *previous* round uses that round's
                            # mask-zeroed (and, under quarantine, post-
                            # screen effective) weights, carried alongside
                            # the unsynced states.
                            states = jax.lax.cond(
                                first, lambda s: s, lambda s: sync(s, w_prev),
                                states)
                            if quar:
                                g_tr, states, losses, _, w_eff = \
                                    self._round_core_raw(
                                        g_tr, frozen, states, round_b, w_r)
                            else:
                                g_tr, states, losses, _ = \
                                    self._round_core_raw(
                                        g_tr, frozen, states, round_b, w_r)
                                w_eff = w_r
                            return (g_tr, states, jnp.zeros((), bool),
                                    w_eff), losses
                        (g_tr, states, _, w_last), losses = jax.lax.scan(
                            body, (global_trainable, opt_states,
                                   jnp.ones((), bool), w_rounds[0]),
                            (bat, w_rounds))
                        return (g_tr, sync(states, w_last)), losses
                else:
                    def scan_rounds_masked(global_trainable, frozen,
                                           opt_states, bat, w_rounds):
                        def body(carry, xs):
                            round_b, w_r = xs
                            g_tr, states = carry
                            g_tr, states, losses, _ = self._round_masked_core(
                                g_tr, frozen, states, round_b, w_r)
                            return (g_tr, states), losses
                        return jax.lax.scan(
                            body, (global_trainable, opt_states),
                            (bat, w_rounds))
                self._rounds_scan_masked = jax.jit(scan_rounds_masked,
                                                   donate_argnums=(0, 2))
            scan_fn = self._rounds_scan_masked
            w_arg = jnp.asarray(np.asarray(w)[None] * masks, w.dtype)
        with jax.set_mesh(self.mesh):
            (self.global_trainable, self.opt_states), losses = \
                scan_fn(self.global_trainable, self.frozen,
                        self.opt_states, batches, w_arg)
        self.round_idx += int(k_rounds)
        return {"losses": losses,                          # (K, C, T)
                "mean_final_loss": float(jnp.mean(losses[-1, :, -1]))}

    # ------------------------------------------------ pipelined rounds ------
    def _pipeline_rounds(self) -> bool:
        """Whether :meth:`run_rounds` scans the one-round-deep pipelined
        schedule: whenever the method syncs. Quarantined scans pipeline too:
        the raw round core returns the post-screen effective weights
        (``return_weights``), which ride the scan carry so the deferred 𝒮
        reproduces the post-quarantine weighting exactly."""
        return self.state_sync != "none"

    def _raw_round(self):
        """Raw (state_sync=None) round core for the pipelined scans: the
        body defers 𝒮 to the top of the next iteration, so the scanned
        round must return unsynced states. One core serves masked and
        unmasked scans — ``exclude_zero_weights`` only alters the in-round
        sync tail, which the raw core never runs (the deferred
        `_make_scan_sync` carries the exclusion instead). Under quarantine
        the core also returns the round's post-screen effective weights
        for the deferred 𝒮 to consume."""
        if self._round_core_raw is None:
            self._round_core_raw = steps_lib.make_fed_round_step(
                self.cfg, self.spec, self.n_clients, state_sync=None,
                return_weights=self.quarantine, **self._step_kwargs)

    def _make_scan_sync(self, exclude_zero: bool):
        """The deferred 𝒮 + install + seed bump used by the pipelined scan
        bodies and the post-scan drain — exactly the fused round's sync tail
        (`steps.sync_client_states`), applied one round late. Weight
        normalization is internal to the sync protocols, so passing the raw
        (mask-zeroed, or post-quarantine effective) round weights is
        equivalent to the in-round normalized weights."""
        def sync(states, w):
            return steps_lib.sync_client_states(
                states, w, self.n_clients, self.state_sync,
                bases_shared=self._bases_shared(),
                exclude_zero_weights=exclude_zero,
                **self._robust_sync_kwargs)
        return sync

    def _bases_shared(self) -> bool:
        """The shared-basis factored sync requires every client on the
        identical basis. With the production ``refresh_mode='random'`` (or
        'auto' with zero adaptive steps, which never takes the data branch)
        every in-step refresh is seeded-random from the broadcast seed —
        shared by construction. 'svd' refreshes from each client's own
        gradient, so bases diverge and the sync takes the heterogeneous
        factored path."""
        return self.spec.refresh_mode != "svd"

"""Federated fine-tuning engine — 𝒯 / 𝒜 / 𝒮 composition (paper §3, Alg. 1).

The one-host engine the benchmark cells and the paper-table benchmarks run:
clients are vectorized with ``jax.vmap`` over a leading client axis (the same
mapping the production runtime realizes as a mesh axis), local steps run under
``jax.lax.scan``, and each method is a (trainable-kind, optimizer,
aggregation, state-sync) 4-tuple per Table 1:

  ============  =========  ===========  ==============  =======
  method        trainable  optimizer 𝒯  aggregation 𝒜   sync 𝒮
  ============  =========  ===========  ==============  =======
  fedavg_full   dense      AdamW        dense avg       none
  fedit         LoRA(A,B)  Adam         factor avg      none
  ffa_lora      LoRA(B)    SGD          factor avg      none
  lora_fair     LoRA(A,B)  SGD          factor avg+ref  none
  flora         LoRA(A,B)  AdamW        lift ΔW, merge  none
  fr_lora       LoRA(A,B)  AdamW        lift ΔW, merge
                                        + rank-r refac  none
  fedgalore-    dense      GaLoreAdamW  dense avg       none
  fedgalore     dense      GaLoreAdamW  dense avg       AJIVE(ṽ)
  ============  =========  ===========  ==============  =======

Execution model
---------------
The default round is **whole-round fused**: InitState (Eq. 5 — fresh moments,
installed synced ṽ, bucketed projector refresh), T local steps, aggregation 𝒜
and state sync 𝒮 lower as ONE jitted program per round, with the persistent
client buffers donated back in every call so XLA reuses their memory for the
round's outputs. For the GaLore methods those buffers are **rank-r factored**:
within a round every local update lives in the shared rank-r subspace, so a
client carries only the (m, r)/(r, n) accumulator ``R_i`` around the broadcast
global base — the local step reads ``W_i = base_scale·W + lift(R_i)``
transiently, decoupled weight decay rides the scalar ``base_scale =
(1-ηλ)^t``, and 𝒜 collapses to ``base_scale·W + Σ wᵢ lift(Rᵢ)`` (O(C·r(m+n))
state and reduction instead of O(C·m·n); see ``galore.factored_adamw_step``).
On top of that the round **streams the cohort in chunks**: with
``FedConfig.client_chunk=B`` the fused program scans over C/B client chunks,
so the dense forward/backward working set scales with B while the factored
per-client results accumulate at O(C·r(m+n)) — cohort size is decoupled from
peak memory (C≈512 on a laptop-class host). 𝒮 never leaves projected
coordinates: shared-basis rounds run the factored protocols, and the adaptive
round-0 diverged-basis case runs the heterogeneous-basis factored sync (r×r
transfer Grams — no dense ``(C, m, n)`` lift anywhere).
:meth:`FedEngine.run_rounds` additionally drives K rounds as a single
``lax.scan`` dispatch for benchmark sweeps. Methods whose trainable is not
all GaLore target blocks (the LoRA and dense baselines) run the same fused
round on dense per-client weight stacks. The eager stage-by-stage round with
dense per-client weights and the dense-lift 𝒮 is not a mode of this engine:
it is ``core.reference.run_round_eager``, which the tests hold this engine
to.

Memory model of the factored round: **lift-free end to end**. The local
step never reads a dense per-leaf weight: target leaves enter the loss as ``models.layers.LowRankDelta`` nodes
whose delta-aware matmul computes ``base_scale·(x@W) + split-matmul(R_i)``
(O(t·r·(m+n)) on top of the base GEMM), and the custom VJP returns the
cotangent for ``R_i`` already in rank-r coordinates — so the factored round
executes **zero** O(m·n·r) lift GEMMs and **zero** dense m×n gradient
cotangents for GaLore target leaves. Global-norm clipping stays exact via
the VJP's dense-norm probes. The transient-lift read (materialize
``base_scale·W + lift(R_i)`` per leaf per step, dense AD, then re-project;
:meth:`FedEngine._local_train_factored_one`) is what the adaptive round 0
runs (a ``lax.cond``): its data-driven RSVD refresh needs the dense
per-client gradient that the lift-free path never builds."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import aggregation as agg
from . import galore as gal
from . import lora as lora_lib
from . import projector as proj
from . import state_sync as sync_lib
from .population import ParticipationConfig
from .. import optim as optim_lib
from ..optim.base import apply_updates

# Host spans of a round in the profiler's trace (``fed.round`` and its
# ``prepare`` / ``dispatch`` / ``readback`` children); the round program's
# own stages are ``jax.named_scope``s, which reach every instruction's
# ``op_name`` in the compiled program.
_span = jax.profiler.TraceAnnotation

PyTree = Any


@dataclasses.dataclass(frozen=True)
class FedMethodSpec:
    name: str
    trainable: str          # 'dense' | 'lora' | 'lora_b' | 'galore'
    optimizer: str          # 'sgd' | 'sgdm' | 'adam' | 'adamw' | 'galore_adamw'
    aggregation: str        # 'dense_avg'|'factor_avg'|'fair'|'lift_merge'|'lift_refac'
    state_sync: str         # 'none' | 'avg' | 'avg_svd' | 'ajive'


METHODS: Dict[str, FedMethodSpec] = {
    "fedavg_full": FedMethodSpec("fedavg_full", "dense", "adamw", "dense_avg", "none"),
    "fedit": FedMethodSpec("fedit", "lora", "adam", "factor_avg", "none"),
    "ffa_lora": FedMethodSpec("ffa_lora", "lora_b", "sgd", "factor_avg", "none"),
    "lora_fair": FedMethodSpec("lora_fair", "lora", "sgd", "fair", "none"),
    "flora": FedMethodSpec("flora", "lora", "adamw", "lift_merge", "none"),
    "fr_lora": FedMethodSpec("fr_lora", "lora", "adamw", "lift_refac", "none"),
    "fedgalore": FedMethodSpec("fedgalore", "galore", "galore_adamw", "dense_avg", "ajive"),
    "fedgalore_minus": FedMethodSpec("fedgalore_minus", "galore", "galore_adamw",
                                     "dense_avg", "none"),
    # extra ablations beyond the paper's table
    "fedgalore_avg": FedMethodSpec("fedgalore_avg", "galore", "galore_adamw",
                                   "dense_avg", "avg"),
    "fedgalore_avg_svd": FedMethodSpec("fedgalore_avg_svd", "galore", "galore_adamw",
                                       "dense_avg", "avg_svd"),
}


@dataclasses.dataclass(frozen=True)
class FedConfig:
    method: str = "fedgalore"
    rank: int = 8
    lora_scale: float = 2.0          # alpha / r
    lr: float = 1e-3
    weight_decay: float = 0.0
    clip_norm: Optional[float] = 1.0   # Assumption 3.8 (bounded G)
    local_steps: int = 8               # T
    rounds: int = 10                   # K
    adaptive_refreshes: int = 2        # S (SVD->random schedule)
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    seed: int = 0
    reset_opt_each_round: bool = True  # 𝒮 'none' => reinit each round
    # The round's reference forms (eager stages, dense per-client weights,
    # dense-lift 𝒮) are functions in core.reference that tests call; no
    # field here selects them.
    # The GaLore step's kernel route (core.galore): None = auto
    # (kernels.ops.use_kernels), True/False force the Pallas kernel on/off.
    use_pallas: Optional[bool] = None
    # client_chunk=B streams the cohort through the fused round in C/B chunks
    # (B must divide C; None = one chunk), bounding the dense transient
    # working set by B clients (module docstring).
    client_chunk: Optional[int] = None
    # Planet-scale participation (core.population module docstring): seeded
    # per-round cohort sampling out of a large virtual client population,
    # plus per-client dropout and straggler-delay fault injection. The
    # compiled round keeps its fixed (C, ·, r) shapes — dropped/straggling
    # clients are masked via :meth:`FedEngine.run_round`'s ``mask`` argument
    # (zero effective weight + AJIVE score exclusion), and straggler updates
    # land k rounds late through ``population.StalenessBuffer`` with
    # ``staleness_decay**delay`` weights. None disables the layer: every
    # round is the always-on full-cohort round (bit-identical to the
    # pre-participation engine). Orchestrated by
    # ``population.PopulationRunner``; the engine itself only consumes the
    # per-round masks.
    participation: Optional[ParticipationConfig] = None
    # Defense-in-depth (core.aggregation robust section): the guarded round
    # program screens/aggregates against corrupted client uploads, entirely
    # in factored coordinates. quarantine=True turns on the in-round screen
    # (non-finite reduction + median-norm outlier test at quarantine_zmax ×
    # the weighted median client norm); failures fold into the exclude-zero
    # mask path — zero renormalized weight in 𝒜, excluded from the AJIVE
    # score Gram in 𝒮, stacks sanitized so 0·NaN never reaches a reduction.
    # robust_agg replaces the weighted mean over factored client deltas in
    # 𝒜: 'norm_clip' (median-of-norms clipping), 'trimmed_mean'
    # (coordinate-wise weighted trim by robust_trim per tail), 'geomedian'
    # (Weiszfeld iterations, capped at robust_iters and converged early at
    # relative tolerance robust_tol); heterogeneous-basis rounds re-base
    # every client's factored stack onto the reference client's basis via
    # the r×r transfer Grams, so the coordinate-wise modes stay
    # well-defined when bases diverge. The same robust mode guards 𝒮: the
    # projected-moment stacks feeding state_sync/ajive are robustly
    # reduced (and quarantined clients' score columns excluded from the
    # joint-basis Gram) before spectral extraction. The guarded program is
    # compiled SEPARATELY — with both knobs at their defaults and no
    # injected attack, rounds run the pre-PR unguarded program, and an
    # all-honest cohort through the guarded program is bit-identical to
    # it (all-pass short-circuit; asserted in tests).
    robust_agg: str = "none"
    quarantine: bool = False
    quarantine_zmax: float = 6.0
    robust_trim: float = 0.2
    robust_iters: int = 8
    robust_tol: float = 1e-6


# ------------------------------------------------------------ trainables ----

def split_trainable(params: PyTree, target_fn) -> tuple:
    """dense/galore trainable: the target matrix leaves themselves (2-D, or
    3-D stacked scan blocks — one projector per layer); the rest frozen."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    train, frozen = [], []
    for path, p in leaves:
        pstr = "/".join(str(getattr(q, "key", getattr(q, "idx", q))) for q in path)
        if p.ndim in (2, 3) and target_fn(pstr, p):
            train.append(p)
            frozen.append(None)
        else:
            train.append(None)
            frozen.append(p)
    return (jax.tree_util.tree_unflatten(treedef, train),
            jax.tree_util.tree_unflatten(treedef, frozen))


def merge_dense(frozen: PyTree, trainable: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda f, t: t if f is None else f, frozen, trainable,
        is_leaf=lambda x: x is None)


def merge_lora(base: PyTree, adapters: PyTree, scale: float,
               freeze_a: bool = False) -> PyTree:
    def merge(p, ad):
        if ad is None:
            return p
        a = jax.lax.stop_gradient(ad.a) if freeze_a else ad.a
        return p + (scale * (ad.b @ a)).astype(p.dtype)
    return jax.tree_util.tree_map(merge, base, adapters,
                                  is_leaf=lora_lib.is_lora_pair)


# -------------------------------------------------------------- the engine --

class FedEngine:
    """Reference federated simulation. ``loss_fn(params, batch) -> scalar``."""

    def __init__(self, cfg: FedConfig, loss_fn: Callable, params: PyTree,
                 target_fn: Callable = None, eval_fn: Callable = None):
        self.cfg = cfg
        self.spec = METHODS[cfg.method]
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.target_fn = target_fn or (lambda p, x: True)
        self.base_params = params
        key = jax.random.PRNGKey(cfg.seed)

        if self.spec.trainable in ("dense", "galore"):
            self.global_trainable, self.frozen = split_trainable(params, self.target_fn)
        else:
            self.global_trainable = lora_lib.tree_lora_init(
                key, params, self.target_fn, cfg.rank)
            self.frozen = params   # LoRA: base stays whole, delta is additive
        if not jax.tree_util.tree_leaves(self.global_trainable):
            raise ValueError(
                f"target_fn selected no trainable leaves for method "
                f"'{cfg.method}' — nothing to train or aggregate")

        self.galore_cfg = gal.GaloreConfig(
            rank=cfg.rank, refresh_every=10 ** 9,   # engine refreshes manually
            adaptive_steps=cfg.adaptive_refreshes, b1=cfg.b1, b2=cfg.b2,
            eps=cfg.eps, refresh_mode="auto", use_pallas=cfg.use_pallas)
        self.tx = self._make_tx()
        # Client axes for the optimizer state: moments/bases are per-client
        # (axis 0); the GaLore step counter and round seed stay UNBATCHED —
        # they are identical across clients by construction, and keeping them
        # scalar keeps the in-step `count % τ` refresh a real `lax.cond`
        # under vmap (a batched predicate would lower to a select that
        # computes the RSVD branch every local step).
        self._opt_axes = self._client_opt_axes()
        self.round_idx = 0
        self.synced_v = None   # lifted+projected ṽ init from 𝒮
        # Factored-delta clients (module docstring): GaLore methods whose
        # trainable is entirely target blocks carry rank-r accumulators
        # instead of dense per-client weight copies in the fused round.
        self._factored = False
        if self.spec.optimizer == "galore_adamw":
            st_shape = jax.eval_shape(
                lambda: self.tx.init(self.global_trainable))
            self._factored = gal.all_blocks_projected(
                gal.galore_state_of(st_shape))
        # Lift-free delta-context local steps whenever the factored client
        # model is (all blocks projected); the adaptive round 0 still takes
        # the transient-lift read inside the round (see _round_core).
        self._lift_free = self._factored
        # Whole-round fused program state: the persistent client buffers —
        # factored (C, ·, r) accumulators or dense (C, m, n) stacks — are
        # donated back into every round call (their memory is reused for
        # the round's outputs), and the jitted round / scan-over-rounds
        # drivers are built lazily on first use.
        self._client_state = None
        self._client_opt = None
        self._round_jit = None
        self._rounds_scan_jit = None
        # Participation-masked variants: same round math on renormalized
        # masked weights, with zero-weight clients additionally excluded
        # from the AJIVE joint-basis estimate. Kept as SEPARATE compiled
        # programs so the unmasked round stays byte-for-byte the program it
        # was before the participation layer existed (full-participation
        # masks short-circuit onto it — bit-identical by construction).
        self._round_masked_jit = None
        self._rounds_scan_masked_jit = None
        # Guarded variants (quarantine / robust_agg / injected attacks):
        # again separate compiled programs, so the default round is
        # byte-for-byte the pre-defense program and honest cohorts through
        # the guard short-circuit onto the same math bit-identically.
        if cfg.robust_agg not in agg.ROBUST_MODES:
            raise ValueError(f"robust_agg={cfg.robust_agg!r} not in "
                             f"{agg.ROBUST_MODES}")
        self._guard_cfg = bool(cfg.quarantine) or cfg.robust_agg != "none"
        if self._guard_cfg and not self._factored:
            raise ValueError(
                "quarantine/robust_agg need the factored client model "
                "(GaLore methods whose trainables are all target blocks) — "
                "the screen and the robust reductions run on rank-r "
                "factored stacks")
        self._round_guard_jit = None
        self._rounds_scan_guard_jit = None
        # Lazy zero (dim, r) basis-shape donor for the pipelined scans'
        # slim pending sync (values never read).
        self._basis_template_tree = None

    # ----------------------------------------------------------- optimizer --
    def _make_tx(self):
        c = self.cfg
        o = self.spec.optimizer
        if o == "sgd":
            return optim_lib.sgd(c.lr, clip_norm=c.clip_norm)
        if o == "sgdm":
            return optim_lib.sgd(c.lr, momentum=0.9, clip_norm=c.clip_norm)
        if o == "adam":
            return optim_lib.adam(c.lr, c.b1, c.b2, c.eps, clip_norm=c.clip_norm)
        if o == "adamw":
            return optim_lib.adamw(c.lr, c.b1, c.b2, c.eps, c.weight_decay,
                                   clip_norm=c.clip_norm)
        if o == "galore_adamw":
            return gal.galore_adamw(self.galore_cfg, c.lr, c.weight_decay,
                                    seed=c.seed, clip_norm=c.clip_norm)
        raise ValueError(o)

    # -------------------------------------------------------------- 𝒯 -------
    def _trainable_loss(self, trainable, batch, frozen):
        if self.spec.trainable in ("dense", "galore"):
            params = merge_dense(frozen, trainable)
        else:
            params = merge_lora(frozen, trainable, self.cfg.lora_scale,
                                freeze_a=(self.spec.trainable == "lora_b"))
        return self.loss_fn(params, batch)

    def _local_train_one(self, trainable, opt_state, batches, frozen):
        """T local steps on one client (lax.scan) — Definition 3.1."""
        def step(carry, batch):
            tr, st = carry
            loss, grads = jax.value_and_grad(self._trainable_loss)(
                tr, batch, frozen)
            updates, st = self.tx.update(grads, st, tr)
            tr = apply_updates(tr, updates)
            return (tr, st), loss
        (trainable, opt_state), losses = jax.lax.scan(
            step, (trainable, opt_state), batches)
        return trainable, opt_state, losses

    @jax.named_scope("fed.init_state")
    def _init_state0(self, round_idx, synced_v, global_trainable):
        """One client's round-start InitState (Eq. 5): fresh moments, install
        the synced ṽ, refresh the projector for the new round (seeded
        broadcast — identical for every client, so the caller broadcasts the
        result along the client axis). jit/scan-safe in ``round_idx``."""
        st = self.tx.init(global_trainable)
        if self.spec.optimizer == "galore_adamw":
            g = gal.galore_state_of(st)
            g = gal.with_seed(g, self.cfg.seed + round_idx)       # s_k
            g = g._replace(count=jnp.asarray(
                round_idx * self.cfg.local_steps, jnp.int32))
            if synced_v is not None:
                g = gal.with_projected_v(g, synced_v)
            g = gal.manual_refresh(self.galore_cfg, g, round_idx)
            st = gal.replace_galore_state(st, g)
        return st

    def _client_opt_axes(self):
        """vmap axes tree for the optimizer state: 0 everywhere except the
        GaLore counter/seed, which stay scalar (see __init__)."""
        st = jax.eval_shape(lambda: self.tx.init(self.global_trainable))
        return gal.client_opt_axes(st)

    @jax.named_scope("fed.init_state")
    def _stack_opt_state(self, st, n_clients: int):
        """Broadcast one InitState along the client axis, honoring the
        unbatched-count/seed layout of :meth:`_client_opt_axes`."""
        return gal.stack_opt_state(st, n_clients)

    # ------------------------------------------------------------ a round ---
    def _normalize_weights(self, weights, k_clients):
        return sync_lib.normalize_weights(weights, k_clients)

    def _masked_weights(self, weights, mask, k_clients):
        """Effective weights of a participation-masked round: the base
        weights with dropped clients zeroed, renormalized over the
        participants — eagerly, so the masked round is exactly the original
        round reweighted onto the participating subset."""
        w = np.asarray(self._normalize_weights(weights, k_clients))
        wm = np.where(np.asarray(mask, bool), w, 0.0)
        s = float(wm.sum())
        if s <= 0.0:
            raise ValueError("participation mask drops every client in the "
                             "cohort — a round needs >= 1 on-time participant")
        return jnp.asarray(wm / s, jnp.float32)

    @staticmethod
    def _canon_mask(mask, k_clients):
        """None | all-true masks collapse to None: full participation runs
        the pre-participation program on the pre-participation inputs
        (bit-identity is by construction, not by numerics)."""
        if mask is None:
            return None
        m = np.asarray(mask, bool).reshape(-1)
        if m.shape != (k_clients,):
            raise ValueError(f"mask shape {m.shape} != cohort ({k_clients},)")
        return None if m.all() else m

    @staticmethod
    def _canon_attack(attack, k_clients):
        """None | all-ones attack vectors collapse to None: an adversary-free
        round never forces the guarded program on its own (a quarantine /
        robust_agg config still does)."""
        if attack is None:
            return None
        a = np.asarray(attack, np.float32).reshape(-1)
        if a.shape != (k_clients,):
            raise ValueError(f"attack shape {a.shape} != cohort "
                             f"({k_clients},)")
        return None if np.all(a == 1.0) else a

    def run_round(self, client_batches: PyTree, weights=None, mask=None,
                  attack=None):
        """client_batches: pytree with leading axes (K clients, T steps, ...).

        Returns dict of metrics. Mutates engine global state. The round is
        the whole-round fused program (one dispatch, donated client
        buffers); its stage-by-stage reference is
        ``core.reference.run_round_eager``.

        ``mask`` (optional bool (K,)) marks this round's on-time
        participants: masked-out clients still occupy their compiled cohort
        slot (shapes never change) but carry zero effective weight in 𝒜 and
        are excluded from the AJIVE joint basis in 𝒮. A full-participation
        mask short-circuits onto the unmasked program — bit-identical to
        calling without a mask.

        ``attack`` (optional float (K,)) injects per-client uplink
        corruption INSIDE the compiled round: each client's factored
        contribution (accumulator, projected moments) is multiplied by its
        entry after the local phase (NaN = corrupted shard, -1 = sign flip,
        s = norm scale attack; see ``population.corruption_multipliers``).
        An all-ones vector short-circuits to no attack. Any attack — or a
        ``quarantine``/``robust_agg`` config — selects the guarded program:
        screen (if quarantine) → sanitize + renormalize → robust 𝒜 →
        exclusion-aware 𝒮. An honest cohort through the guarded program is
        bit-identical to the unguarded one.
        """
        with _span("fed.round", round=self.round_idx):
            with _span("fed.round.prepare"):
                k_clients = jax.tree_util.tree_leaves(
                    client_batches)[0].shape[0]
                mask = self._canon_mask(mask, k_clients)
                attack = self._canon_attack(attack, k_clients)
                guarded = self._guard_cfg or attack is not None
                w = (self._normalize_weights(weights, k_clients)
                     if mask is None
                     else self._masked_weights(weights, mask, k_clients))
                extra = ()
                if guarded:
                    round_fn = self._round_guard_jitted()
                    a = (np.ones((k_clients,), np.float32)
                         if attack is None else attack)
                    extra = (jnp.asarray(a, jnp.float32),)
                elif mask is None:
                    round_fn = self._round_jitted()
                else:
                    round_fn = self._round_masked_jitted()
                self._ensure_client_buffers(k_clients)
                round_idx = jnp.asarray(self.round_idx, jnp.int32)
            with _span("fed.round.dispatch"):
                out = round_fn(
                    self._client_state, self._client_opt,
                    self.global_trainable, self.frozen, self.synced_v,
                    round_idx, client_batches, w, *extra)
            if self._frozen_mutates():
                (self._client_state, self._client_opt, self.global_trainable,
                 self.frozen, self.synced_v, losses) = out
            else:
                (self._client_state, self._client_opt, self.global_trainable,
                 self.synced_v, losses) = out
            self.round_idx += 1
            with _span("fed.round.readback"):
                mean_final = float(jnp.mean(losses[:, -1]))
        return {"local_loss": losses,                      # (K, T)
                "mean_final_loss": mean_final}

    def lower_round(self, client_batches: PyTree, weights=None):
        """The default round program (unmasked, unguarded, fused) lowered
        for these batches against the engine's current state, without
        running it — for reading its compiled program."""
        k_clients = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
        self._ensure_client_buffers(k_clients)
        return self._round_jitted().lower(
            self._client_state, self._client_opt, self.global_trainable,
            self.frozen, self.synced_v,
            jnp.asarray(self.round_idx, jnp.int32), client_batches,
            self._normalize_weights(weights, k_clients))

    def run_rounds(self, round_batches: PyTree, weights=None, masks=None):
        """K rounds as ONE dispatch: ``lax.scan`` over the fused round.

        round_batches: pytree with leading (K rounds, C clients, T steps, ...)
        axes. Returns dict with ``local_loss`` of shape (K, C, T). Mutates
        engine global state exactly as K successive :meth:`run_round` calls.

        ``masks`` (optional bool (K rounds, C)) applies a per-round
        participation mask: the per-round effective weights are renormalized
        eagerly (pure host function of the masks — reproducible between this
        scan driver and K :meth:`run_round` calls) and ride the scan as xs.
        All-true masks short-circuit onto the unmasked scan program.
        Staleness is NOT expressible inside the scan (stale merges mutate
        the carry between rounds on the host) — ``population.
        PopulationRunner`` falls back to sequential rounds when a staleness
        buffer is active.
        """
        leading = jax.tree_util.tree_leaves(round_batches)[0].shape
        k_rounds, k_clients = leading[0], leading[1]
        if masks is not None:
            masks = np.asarray(masks, bool)
            if masks.shape != (int(k_rounds), int(k_clients)):
                raise ValueError(f"masks shape {masks.shape} != "
                                 f"({k_rounds}, {k_clients})")
            if masks.all():
                masks = None
        # Attack injection is not expressible inside the scan driver (a
        # per-round attack would ride the xs, but corruption plans come from
        # PopulationRunner, which drives sequential rounds anyway) — the
        # guarded scan exists so a quarantine/robust_agg config still gets
        # the one-dispatch sweep, guarding every round with a unit attack.
        with _span("fed.round", round=self.round_idx, rounds=int(k_rounds)):
            with _span("fed.round.prepare"):
                if masks is None and not self._guard_cfg:
                    w = self._normalize_weights(weights, k_clients)
                    scan_fn = self._rounds_scan_jitted()
                else:
                    # Per-round effective weights as scan xs; exclusion-aware
                    # 𝒮.
                    if masks is None:
                        w_one = self._normalize_weights(weights, k_clients)
                        w = jnp.tile(w_one[None], (int(k_rounds), 1))
                    else:
                        w = jnp.stack([
                            self._masked_weights(weights, m, k_clients)
                            for m in masks])
                    scan_fn = (self._rounds_scan_guard_jitted()
                               if self._guard_cfg
                               else self._rounds_scan_masked_jitted())
                synced_v = self.synced_v
                if synced_v is None and self._method_syncs():
                    # Uniform scan carry: a zero synced ṽ is bit-identical to
                    # "no synced state" (fresh moments are zero and the
                    # install clamps at zero), so round 0 inside the scan
                    # matches run_round.
                    synced_v = self._zero_synced_template()
                round_idx = jnp.asarray(self.round_idx, jnp.int32)
            with _span("fed.round.dispatch"):
                carry, losses = scan_fn(
                    self.global_trainable, self.frozen, synced_v, round_idx,
                    round_batches, w)
            if self._frozen_mutates():
                self.global_trainable, self.frozen, new_synced, _ = carry
            else:
                self.global_trainable, new_synced, _ = carry
            if self._method_syncs():
                self.synced_v = new_synced
            self.round_idx += int(k_rounds)
            with _span("fed.round.readback"):
                mean_final = float(jnp.mean(losses[-1, :, -1]))
        return {"local_loss": losses,                      # (K, C, T)
                "mean_final_loss": mean_final}

    def _build_rounds_scan(self, exclude_zero: bool, guard: bool = False,
                           pipelined: bool = False):
        """jit a scan-over-rounds driver. Unmasked: one weight vector closed
        into every round (scan-invariant). Masked (``exclude_zero``): one
        effective weight vector per round rides the xs, and 𝒮 excludes
        zero-weight clients from the joint-basis estimate. ``guard`` runs
        every round through the quarantine/robust-𝒜 program (unit attack —
        per-round injected attacks don't ride the scan).

        ``pipelined`` (every method that syncs, :meth:`_method_syncs`) is
        the one-round-deep software pipeline: every round *defers* its 𝒮
        install by returning the slim pending payload ``(tree, w_eff)``
        (:meth:`_slim_payload` — protocol-aware: the weighted-mean
        protocols reduce in-body and carry the small synced tree, ajive
        carries the per-client projected-moment stacks its joint basis
        needs), which the next round's body drains at its
        top (:meth:`_sync_pending`); a post-scan epilogue drains the last
        round. Round k+1 still consumes exactly round k's synced moments —
        the schedule is numerically the sequential program, re-associated
        so the deferred eigh chain only gates the *first optimizer-moment
        read* of the next local phase (the gradient work before it is
        independent and free to overlap). The carry stays slim: the
        per-client basis stacks never ride the scan boundary — when the
        call's first round may hold heterogeneous bases (adaptive round 0),
        that one round runs its transfer-Gram 𝒮 inline inside its own body
        and parks the small synced tree in a carried slot instead. Both
        schedules run as one uniform scan of the same length (splitting
        rounds across scans of different lengths changes XLA's loop
        compilation). Methods without 𝒮 take the sequential body. Tests
        hold both schedules to K successive :meth:`run_round` calls."""
        frozen_mutates = self._frozen_mutates()
        # Robust-𝒮 rides the guarded program only: the deferred 𝒮 drains
        # (and the hetero0 inline sync) must reduce the projected-moment
        # stacks with the same robust mode the in-body rounds use, so the
        # pipelined guarded scan stays numerically the sequential guarded
        # program. Unguarded scans keep robust="none" — bit-identity with
        # the pre-robust program.
        robust = self.cfg.robust_agg if guard else "none"
        if pipelined:
            # Build the slim-sync basis template eagerly: materialized under
            # an active trace it would cache tracers (omnistaging) instead
            # of the concrete scan-invariant constant.
            self._basis_template()

        def scan_rounds(global_tr, frozen, synced_v, round_idx, batches, w):
            # frozen rides in the carry only for the lift aggregations
            # that rewrite it; otherwise it is scan-invariant (closed
            # over by the body — no per-iteration copy).
            k_rounds = jax.tree_util.tree_leaves(batches)[0].shape[0]
            xs = (batches, w) if exclude_zero else batches

            def run_round(g_tr, fz, sv, ridx, round_b, w_r, skip):
                kw = {}
                if guard:
                    kc = jax.tree_util.tree_leaves(round_b)[0].shape[0]
                    kw["attack"] = jnp.ones((kc,), jnp.float32)
                _, _, g_tr, fz, out_sv, losses = self._round_core(
                    g_tr, fz, sv, ridx, round_b, w_r,
                    exclude_zero=exclude_zero, skip_sync=skip, **kw)
                return g_tr, fz, out_sv, losses

            def seq_body(carry, x):
                round_b, w_r = x if exclude_zero else (x, w)
                if frozen_mutates:
                    g_tr, fz, sv, ridx = carry
                else:
                    (g_tr, sv, ridx), fz = carry, frozen
                g_tr, fz, sv, losses = run_round(
                    g_tr, fz, sv, ridx, round_b, w_r, skip=False)
                new_carry = ((g_tr, fz, sv, ridx + 1) if frozen_mutates
                             else (g_tr, sv, ridx + 1))
                return new_carry, losses

            carry0 = ((global_tr, frozen, synced_v, round_idx)
                      if frozen_mutates
                      else (global_tr, synced_v, round_idx))
            if not pipelined:
                return jax.lax.scan(seq_body, carry0, xs)

            # One uniform scan for the pipelined schedule too: the bodies
            # differ from seq_body only around 𝒮, so the local phases
            # compile in-loop exactly as the sequential body's do
            # (splitting rounds across scans of different lengths changes
            # XLA's loop compilation).
            # hetero0: the call's first round may hold heterogeneous bases
            # (adaptive refresh) AND the payload defers per-client stacks
            # whose drain is shared-basis-only — that round must sync
            # inline into a carried slot. The weighted-mean protocols'
            # payload is the fully synced tree (round-0 cond included), so
            # they never need the slot.
            hetero0 = (self.galore_cfg.adaptive_steps > 0
                       and self.galore_cfg.refresh_mode != "random"
                       and not self._slim_reduces_in_body())
            k_clients = jax.tree_util.tree_leaves(batches)[0].shape[1]

            def pipe_body(carry, x):
                round_b, w_r = x if exclude_zero else (x, w)
                if frozen_mutates:
                    if hetero0:
                        g_tr, fz, pend, sv0, ridx = carry
                    else:
                        g_tr, fz, pend, ridx = carry
                else:
                    fz = frozen
                    if hetero0:
                        g_tr, pend, sv0, ridx = carry
                    else:
                        g_tr, pend, ridx = carry
                pv, pw = pend

                def drain(_):
                    # Drain the previous round's slim pending payload here,
                    # at the top of this round's body, so its eigh chain
                    # sits adjacent to this round's independent gradient
                    # work. The first round of the call adopts the entry
                    # synced_v (outer cond); under hetero0 the second round
                    # adopts the first's inline sv0 instead (its bases may
                    # have diverged — the slim shared drain doesn't apply).
                    if not hetero0:
                        return self._sync_pending(pv, pw, exclude_zero,
                                                  robust=robust)
                    return jax.lax.cond(
                        ridx == round_idx + 1, lambda _: sv0,
                        lambda _: self._sync_pending(pv, pw, exclude_zero,
                                                     robust=robust),
                        operand=None)

                sv = jax.lax.cond(ridx == round_idx, lambda _: synced_v,
                                  drain, operand=None)
                kw = {}
                if guard:
                    kc = jax.tree_util.tree_leaves(round_b)[0].shape[0]
                    kw["attack"] = jnp.ones((kc,), jnp.float32)
                _, out_opt, g_tr, fz, pend_new, losses = self._round_core(
                    g_tr, fz, sv, ridx, round_b, w_r,
                    exclude_zero=exclude_zero, skip_sync=True, **kw)
                if hetero0:
                    def inline0(_):
                        # Possibly-heterogeneous first round of the call:
                        # run its transfer-Gram-capable 𝒮 inline (post-guard
                        # effective weights ride pend_new) — the per-client
                        # basis stacks never enter the carry.
                        v_t, b_t = self._sync_uplink(out_opt)
                        return self._sync_states_from_uplink(
                            v_t, b_t, pend_new[1], ridx, exclude_zero,
                            robust=robust)
                    sv0 = jax.lax.cond(ridx == round_idx, inline0,
                                       lambda _: sv0, operand=None)
                    new_carry = ((g_tr, fz, pend_new, sv0, ridx + 1)
                                 if frozen_mutates
                                 else (g_tr, pend_new, sv0, ridx + 1))
                else:
                    new_carry = ((g_tr, fz, pend_new, ridx + 1)
                                 if frozen_mutates
                                 else (g_tr, pend_new, ridx + 1))
                return new_carry, losses

            pend_0 = self._zero_slim_template(k_clients)
            if hetero0:
                slots = (pend_0, self._zero_synced_template())
            else:
                slots = (pend_0,)
            carry0 = ((global_tr, frozen) + slots + (round_idx,)
                      if frozen_mutates
                      else (global_tr,) + slots + (round_idx,))
            carry, losses = jax.lax.scan(pipe_body, carry0, xs)
            if frozen_mutates:
                g_tr, fz = carry[0], carry[1]
                rest = carry[2:]
            else:
                g_tr, fz = carry[0], frozen
                rest = carry[1:]
            pend, ridx = rest[0], rest[-1]
            # Epilogue: drain the last round's pending payload so the
            # returned carry matches the sequential schedule
            # state-for-state. A single-round hetero0 call never deferred
            # past its inline sv0.
            if hetero0 and k_rounds == 1:
                sv = rest[1]
            else:
                pv, pw = pend
                sv = self._sync_pending(pv, pw, exclude_zero, robust=robust)
            carry = ((g_tr, fz, sv, ridx) if frozen_mutates
                     else (g_tr, sv, ridx))
            return carry, losses
        return jax.jit(scan_rounds)

    def _zero_slim_template(self, k_clients: int):
        """Zero-filled slim pending payload ``(tree, w)`` for ``k_clients``
        — the pipelined scan's initial pending slot (shape donor only; the
        first iteration adopts the entry synced_v instead of draining it).
        The tree matches :meth:`_slim_payload`: reduced (no client axis)
        for the weighted-mean protocols, (C, ·, r) stacks for ajive."""
        w0 = jnp.zeros((k_clients,), jnp.float32)
        if self._slim_reduces_in_body():
            return (self._zero_synced_template(), w0)
        st = jax.eval_shape(lambda: self.tx.init(self.global_trainable))
        v = gal.extract_projected_v(gal.galore_state_of(st))
        return (jax.tree_util.tree_map(
                    lambda x: None if x is None else jnp.zeros(
                        (k_clients,) + x.shape, x.dtype),
                    v, is_leaf=lambda x: x is None),
                w0)

    def _rounds_scan_jitted(self):
        if self._rounds_scan_jit is None:
            self._rounds_scan_jit = self._build_rounds_scan(
                exclude_zero=False, pipelined=self._method_syncs())
        return self._rounds_scan_jit

    def _rounds_scan_masked_jitted(self):
        if self._rounds_scan_masked_jit is None:
            self._rounds_scan_masked_jit = self._build_rounds_scan(
                exclude_zero=True, pipelined=self._method_syncs())
        return self._rounds_scan_masked_jit

    def _rounds_scan_guard_jitted(self):
        if self._rounds_scan_guard_jit is None:
            self._rounds_scan_guard_jit = self._build_rounds_scan(
                exclude_zero=True, guard=True,
                pipelined=self._method_syncs())
        return self._rounds_scan_guard_jit

    # ------------------------------------------------- fused round program --
    def _method_syncs(self) -> bool:
        return (self.spec.state_sync != "none"
                and self.spec.optimizer == "galore_adamw")

    def _zero_synced_template(self):
        st = jax.eval_shape(lambda: self.tx.init(self.global_trainable))
        v_tree = gal.extract_projected_v(gal.galore_state_of(st))
        return jax.tree_util.tree_map(
            lambda x: None if x is None else jnp.zeros(x.shape, x.dtype),
            v_tree, is_leaf=lambda x: x is None)

    def _ensure_client_buffers(self, k_clients: int):
        """Allocate the persistent client buffers once; every fused round
        donates them back and adopts the round's outputs. Factored clients
        persist the rank-r (C, ·, r) accumulator stacks (O(C·r(m+n)) bytes);
        the LoRA and dense baselines persist their (C, ·) trainable
        stacks."""
        have = (self._client_state is not None
                and jax.tree_util.tree_leaves(
                    self._client_state)[0].shape[0] == k_clients)
        if have:
            return
        # Shapes only — no device work: the buffer values are never read
        # (InitState rebuilds them inside the round program).
        st = jax.eval_shape(lambda: self._stack_opt_state(
            self._init_state0(0, None, self.global_trainable), k_clients))
        zeros = lambda s: jnp.zeros(s.shape, s.dtype)
        if self._factored:
            # The stacked moments already carry the (C, ·, r) accumulator
            # shapes — the factored client buffer mirrors them.
            self._client_state = gal.zero_client_deltas(
                gal.galore_state_of(st))
        else:
            self._client_state = jax.tree_util.tree_map(
                lambda x: jnp.zeros((k_clients,) + x.shape, x.dtype),
                self.global_trainable)
        self._client_opt = jax.tree_util.tree_map(zeros, st)

    def client_buffer_bytes(self) -> int:
        """Bytes held by the persistent per-client round buffers (the cohort
        memory the factored representation shrinks) — the bench metric."""
        total = 0
        for tree in (self._client_state, self._client_opt):
            if tree is not None:
                total += sum(x.nbytes
                             for x in jax.tree_util.tree_leaves(tree))
        return total

    def _chunk_size(self, k_clients: int) -> int:
        b = self.cfg.client_chunk or k_clients
        if k_clients % b:
            raise ValueError(f"client_chunk={b} must divide the cohort size "
                             f"{k_clients}")
        return b

    def _local_train_factored_one(self, deltas, opt_state, batches, frozen,
                                  global_trainable):
        """T factored local steps on one client (lax.scan): the client never
        holds a persistent dense weight copy — every step reads
        ``base_scale·W_global + lift(R_i)`` transiently and updates only the
        rank-r accumulator (galore.factored_adamw_step)."""
        c = self.cfg

        def step(carry, batch):
            dl, scale, st = carry
            tr = gal.lift_client_trainable(global_trainable, dl,
                                           gal.galore_state_of(st), scale)
            loss, grads = jax.value_and_grad(self._trainable_loss)(
                tr, batch, frozen)
            dl, scale, st = gal.factored_adamw_step(
                self.galore_cfg, grads, st, dl, scale, lr=c.lr,
                weight_decay=c.weight_decay, clip_norm=c.clip_norm)
            return (dl, scale, st), loss

        (deltas, scale, opt_state), losses = jax.lax.scan(
            step, (deltas, jnp.ones([], jnp.float32), opt_state), batches)
        return deltas, opt_state, losses, scale

    def _local_train_liftfree_one(self, deltas, opt_state, batches, frozen,
                                  global_trainable):
        """T lift-free local steps on one client (lax.scan): target leaves
        enter the loss as LowRankDelta nodes — the forward is the split-
        matmul delta read, the backward returns the R_i cotangent already in
        rank-r coordinates plus exact dense-norm probes for clipping, and
        the step consumes them with the projection GEMM skipped
        (galore.factored_adamw_step on a LiftFreeGrads bundle). The in-step
        refresh is hoisted before the forward (galore.maybe_refresh_instep)
        so cotangents arrive on the refreshed basis — seeded-random only,
        which is why the adaptive round 0 runs the transient-lift read
        (:meth:`_local_train_factored_one`) instead."""
        c = self.cfg

        def step(carry, batch):
            dl, scale, st = carry
            g0 = gal.maybe_refresh_instep(self.galore_cfg,
                                          gal.galore_state_of(st))
            st = gal.replace_galore_state(st, g0)
            loss, grads = gal.liftfree_value_and_grad(
                lambda tr: self._trainable_loss(tr, batch, frozen),
                global_trainable, dl, g0, scale)
            dl, scale, st = gal.factored_adamw_step(
                self.galore_cfg, grads, st, dl, scale, lr=c.lr,
                weight_decay=c.weight_decay, clip_norm=c.clip_norm)
            return (dl, scale, st), loss

        (deltas, scale, opt_state), losses = jax.lax.scan(
            step, (deltas, jnp.ones([], jnp.float32), opt_state), batches)
        return deltas, opt_state, losses, scale

    def _round0_adaptive(self) -> bool:
        """Whether round 0's in-step refresh is data-driven (RSVD of each
        client's own dense gradient) — the one case the lift-free read
        cannot serve and the transient-lift read handles via lax.cond."""
        return (self.galore_cfg.adaptive_steps > 0
                and self.galore_cfg.refresh_mode != "random")

    @jax.named_scope("fed.aggregate")
    def _aggregate_factored(self, global_trainable, out_deltas, out_opt,
                            base_scales, w, round_idx, robust: str = "none"):
        """𝒜 for factored clients: ``(Σᵢ wᵢ sᵢ)·W + Σᵢ wᵢ lift(Rᵢ, Bᵢ)`` per
        target leaf (``sᵢ`` the per-client decayed base scales — identical
        under a constant lr, per-client under a schedule). Shared-basis
        rounds reduce in projected coordinates and lift once; the adaptive
        round-0 diverged-basis case contracts the per-client lifts
        client-by-client (a ``lax.cond``, mirroring
        :meth:`_sync_states_pure`) — no (C, m, n) stack either way.
        ``robust`` swaps the weighted mean over the factored stacks for a
        robust reduction (``aggregation.robust_factored_lift``; 'none' is
        exactly the plain path)."""
        bases = gal.extract_bases(gal.galore_state_of(out_opt))
        round0_hetero = (self.galore_cfg.adaptive_steps > 0
                         and self.galore_cfg.refresh_mode != "random")
        sbar = jnp.einsum("c,c->", w, base_scales.astype(jnp.float32))

        def one(w0, d_stack, b_stack):
            side = (proj.RIGHT if d_stack.shape[-1] == b_stack.shape[-1]
                    else proj.LEFT)

            def shared(_):
                return agg.robust_factored_lift(
                    d_stack, b_stack, side, w, robust, hetero=False,
                    trim=self.cfg.robust_trim, iters=self.cfg.robust_iters,
                    tol=self.cfg.robust_tol)

            def hetero(_):
                return agg.robust_factored_lift(
                    d_stack, b_stack, side, w, robust, hetero=True,
                    trim=self.cfg.robust_trim, iters=self.cfg.robust_iters,
                    tol=self.cfg.robust_tol)

            if round0_hetero:
                lifted = jax.lax.cond(round_idx == 0, hetero, shared,
                                      operand=None)
            else:
                lifted = shared(None)
            return (sbar * w0.astype(jnp.float32) + lifted).astype(w0.dtype)

        return jax.tree_util.tree_map(one, global_trainable, out_deltas,
                                      bases)

    @jax.named_scope("fed.guard")
    def _apply_guard(self, out_d, out_opt, scales, w, attack):
        """The in-round defense gate, between the local phase and 𝒜/𝒮.

        1. Adversary injection: each client's uplink — factored accumulators
           AND projected moments — is multiplied by its ``attack`` entry
           (1.0 for honest clients: bitwise no-op).
        2. Quarantine screen (``cfg.quarantine``): non-finite + median-norm
           outlier test over the factored contributions
           (``aggregation.screen_factored_clients``). Failing clients are
           folded into the exclude-zero mask path — weights zeroed and
           renormalized over the survivors, stacks/scales sanitized so
           0·NaN never reaches a weighted reduction, moments zeroed out of
           the AJIVE score Gram. An all-pass verdict leaves every operand
           bitwise untouched (the honest short-circuit).

        Returns (out_d, out_opt, scales, w, quarantined_count).
        """
        tmap = jax.tree_util.tree_map
        ab = lambda x: attack.astype(jnp.float32).reshape(
            (-1,) + (1,) * (x.ndim - 1))
        out_d = tmap(lambda x: (x.astype(jnp.float32) * ab(x)).astype(
            x.dtype), out_d)
        g = gal.galore_state_of(out_opt)
        v_tree = tmap(
            lambda x: None if x is None
            else (x.astype(jnp.float32) * ab(x)).astype(x.dtype),
            gal.extract_projected_v(g), is_leaf=lambda x: x is None)
        n_quar = jnp.zeros([], jnp.int32)
        if self.cfg.quarantine:
            keep = agg.screen_factored_clients(
                out_d, v_tree, scales, w, zmax=self.cfg.quarantine_zmax)
            out_d = agg.mask_client_rows(out_d, keep)
            v_tree = agg.mask_client_rows(v_tree, keep)
            scales = jnp.where(keep, scales, 1.0)   # enters the sbar einsum
            w = agg.quarantine_weights(w, keep)
            n_quar = jnp.sum((~keep).astype(jnp.int32))
        out_opt = gal.replace_galore_state(out_opt,
                                           gal.with_projected_v(g, v_tree))
        return out_d, out_opt, scales, w, n_quar

    def _round_core(self, global_trainable, frozen, synced_v, round_idx,
                    client_batches, w, exclude_zero: bool = False,
                    attack=None, skip_sync: bool = False):
        """The whole federated round as a pure function: InitState → T local
        steps (vmapped clients, streamed over cohort chunks) → 𝒜 → factored
        𝒮. Shared by the per-round jitted program and the scan-over-rounds
        driver. ``exclude_zero`` is the participation-masked variant: w is a
        masked+renormalized weight vector and 𝒮 drops zero-weight clients
        from the AJIVE joint basis (𝒜 needs no flag — zero weights already
        vanish from every weighted reduction).

        Chunk streaming: the cohort is reshaped (C, …) → (C/B, B, …) and a
        ``lax.scan`` runs the B-client vmapped local phase per chunk, so the
        dense forward/backward working set is bounded by B clients while the
        per-client results — factored accumulators, projected moments,
        losses — stack to the full (C, …) cohort (each client's computation
        is independent, so chunked ≡ unchunked client-for-client). 𝒜 and 𝒮
        then run once on the full factored stacks, keeping them bit-identical
        across chunk sizes.

        ``attack`` (guarded variant only) is the (C,) per-client corruption
        multiplier injected after the local phase; its presence also arms
        the quarantine screen and robust 𝒜/𝒮 per the config
        (:meth:`_apply_guard`; the same ``robust_agg`` mode guards the
        projected-moment reductions inside 𝒮).

        ``skip_sync`` is the pipelined-scan building block: instead of
        installing 𝒮's result here, the ``new_synced`` slot returns the
        round's *slim* pending payload ``(tree, w_eff)`` (see
        :meth:`_slim_payload` — the reduced synced tree for the
        weighted-mean protocols, the projected-moment stacks for ajive,
        plus the post-guard effective weights) for the caller to drain at
        the top of the next round's body (or in the post-scan epilogue)
        via :meth:`_sync_pending`. The slim payload is shared-basis-only
        (no per-client basis stacks ride the scan carry); the possibly
        heterogeneous adaptive round 0 is handled by the pipelined caller
        syncing that round inline from the full uplink. Same math,
        re-associated across the round boundary."""
        if attack is not None and not self._factored:
            raise ValueError("the guarded round requires factored clients")
        k_clients = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
        b = self._chunk_size(k_clients)
        n_chunks = k_clients // b
        st0 = self._init_state0(round_idx, synced_v, global_trainable)
        opt0 = self._stack_opt_state(st0, b)

        @jax.named_scope("fed.local")
        def stream(local_fn, batches):
            """Run the B-client vmapped local phase over the cohort: directly
            for a single chunk, as a lax.scan over C/B chunks otherwise, and
            reassemble the full (C, …) stacks either way."""
            if n_chunks == 1:
                return local_fn(batches)
            cb = jax.tree_util.tree_map(
                lambda x: x.reshape((n_chunks, b) + x.shape[1:]), batches)
            _, out = jax.lax.scan(
                lambda carry, batch_c: (carry, local_fn(batch_c)), None, cb)
            unchunk = lambda x: x.reshape((k_clients,) + x.shape[2:])
            out_x, opt_s, loss_s = out[0], out[1], out[2]
            merged = (jax.tree_util.tree_map(unchunk, out_x),
                      gal.unchunk_opt_state(opt_s, k_clients),
                      unchunk(loss_s))
            if len(out) == 4:                     # factored: (C,) base scales
                merged += (out[3].reshape((k_clients,)),)
            return merged

        if self._factored:
            deltas0 = self._stack_deltas0(st0, b)

            def vmapped(fn):
                return jax.vmap(fn, in_axes=(0, self._opt_axes, 0, None,
                                             None),
                                out_axes=(0, self._opt_axes, 0, 0))

            def transient_fn(batch_c):
                return vmapped(self._local_train_factored_one)(
                    deltas0, opt0, batch_c, frozen, global_trainable)

            def liftfree_fn(batch_c):
                return vmapped(self._local_train_liftfree_one)(
                    deltas0, opt0, batch_c, frozen, global_trainable)

            if self._round0_adaptive():
                # Round 0's data-driven refresh needs dense gradients; every
                # later round runs lift-free. Same output pytree both ways.
                def local_fn(batch_c):
                    return jax.lax.cond(round_idx == 0, transient_fn,
                                        liftfree_fn, batch_c)
            else:
                local_fn = liftfree_fn

            out_d, out_opt, losses, scales = stream(local_fn, client_batches)
            robust = "none"
            if attack is not None:
                out_d, out_opt, scales, w, _ = self._apply_guard(
                    out_d, out_opt, scales, w, attack)
                robust = self.cfg.robust_agg
            new_global = self._aggregate_factored(
                global_trainable, out_d, out_opt, scales, w, round_idx,
                robust=robust)
            if skip_sync:
                new_synced = (self._slim_payload(out_opt, w, round_idx,
                                                 exclude_zero,
                                                 robust=robust), w)
            else:
                new_synced = self._sync_states_pure(out_opt, w, round_idx,
                                                    exclude_zero,
                                                    robust=robust)
            return out_d, out_opt, new_global, frozen, new_synced, losses

        with jax.named_scope("fed.init_state"):
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (b,) + x.shape),
                global_trainable)

        def local_fn(batch_c):
            return jax.vmap(
                self._local_train_one, in_axes=(0, self._opt_axes, 0, None),
                out_axes=(0, self._opt_axes, 0))(
                stacked, opt0, batch_c, frozen)

        out_tr, out_opt, losses = stream(local_fn, client_batches)
        new_global, new_frozen = self._aggregate_pure(out_tr, w, frozen,
                                                      round_idx)
        if skip_sync:
            new_synced = (self._slim_payload(out_opt, w, round_idx,
                                             exclude_zero), w)
        else:
            new_synced = self._sync_states_pure(out_opt, w, round_idx,
                                                exclude_zero)
        return out_tr, out_opt, new_global, new_frozen, new_synced, losses

    @jax.named_scope("fed.init_state")
    def _stack_deltas0(self, st0, n: int):
        """Zero round-start factored accumulators for n clients."""
        d0 = gal.zero_client_deltas(gal.galore_state_of(st0))
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype), d0)

    def _frozen_mutates(self) -> bool:
        """Only the lift aggregations (FLoRA / FR-LoRA) write the frozen
        base; every other method's frozen is round-invariant, so the fused
        programs take it as a plain input and never emit it as an output
        (an undonated output would memcpy the whole base every round)."""
        return self.spec.aggregation in ("lift_merge", "lift_refac")

    def _build_round_jit(self, exclude_zero: bool, guard: bool = False):
        frozen_mutates = self._frozen_mutates()

        if guard:
            def round_fn(client_tr, client_opt, global_trainable, frozen,
                         synced_v, round_idx, client_batches, w, attack):
                del client_tr, client_opt
                out = self._round_core(global_trainable, frozen, synced_v,
                                       round_idx, client_batches, w,
                                       exclude_zero=True, attack=attack)
                if frozen_mutates:
                    return out
                out_tr, out_opt, new_global, _, new_synced, losses = out
                return out_tr, out_opt, new_global, new_synced, losses
            return jax.jit(round_fn, donate_argnums=(0, 1))

        def round_fn(client_tr, client_opt, global_trainable, frozen,
                     synced_v, round_idx, client_batches, w):
            # client_tr/client_opt are donated carries: their values are
            # never read (InitState rebuilds both), only their buffers
            # are reused for this round's stacked outputs.
            del client_tr, client_opt
            out = self._round_core(global_trainable, frozen, synced_v,
                                   round_idx, client_batches, w,
                                   exclude_zero=exclude_zero)
            if frozen_mutates:
                return out
            out_tr, out_opt, new_global, _, new_synced, losses = out
            return out_tr, out_opt, new_global, new_synced, losses
        return jax.jit(round_fn, donate_argnums=(0, 1))

    def _round_jitted(self):
        if self._round_jit is None:
            self._round_jit = self._build_round_jit(exclude_zero=False)
        return self._round_jit

    def _round_masked_jitted(self):
        """The participation-masked round program: identical math on the
        masked+renormalized weights, plus AJIVE score exclusion in 𝒮.
        Compiled separately so the unmasked program never changes."""
        if self._round_masked_jit is None:
            self._round_masked_jit = self._build_round_jit(exclude_zero=True)
        return self._round_masked_jit

    def _round_guard_jitted(self):
        """The guarded round program: attack injection → quarantine screen →
        robust 𝒜 → exclusion-aware 𝒮, always exclude-zero (quarantined
        clients fold into the same mask path as dropped ones). Compiled
        separately; honest cohorts through it are bit-identical to the
        unguarded program (all-pass short-circuit — asserted in tests)."""
        if self._round_guard_jit is None:
            self._round_guard_jit = self._build_round_jit(
                exclude_zero=True, guard=True)
        return self._round_guard_jit

    # -------------------------------------------------------------- 𝒜 -------
    @jax.named_scope("fed.aggregate")
    def _aggregate_pure(self, stacked, w, frozen, round_idx):
        """Aggregation 𝒜 as a pure function of the client-stacked trainables:
        returns (new_global_trainable, new_frozen)."""
        s = self.spec.aggregation
        c = self.cfg
        if s == "dense_avg":
            return agg.dense_delta_average(stacked, w), frozen
        if s == "factor_avg":
            return agg.factor_average(stacked, w), frozen
        if s == "fair":
            return agg.lora_fair_refine(stacked, w, c.lora_scale), frozen
        if s in ("lift_merge", "lift_refac"):
            deltas = agg.lift_average(stacked, w, c.lora_scale)
            if s == "lift_merge":
                # FLoRA: the full-rank average reaches every client via the
                # merged base; adapters restart from zero.
                frozen = jax.tree_util.tree_map(
                    lambda p, d: p if d is None else p + d.astype(p.dtype),
                    frozen, deltas, is_leaf=lambda x: x is None)
                return self._fresh_adapters(round_idx), frozen
            # FR-LoRA: rank-r refactorization carries what fits in the
            # adapters; the residual merges into the base (kept, not lost).
            new_ad, resid = [], []
            dl, treedef = jax.tree_util.tree_flatten(
                deltas, is_leaf=lambda x: x is None)
            for d in dl:
                if d is None:
                    new_ad.append(None)
                    resid.append(None)
                else:
                    pair = lora_lib.svd_truncate(d / max(c.lora_scale, 1e-12),
                                                 c.rank)
                    new_ad.append(pair)
                    resid.append(d - c.lora_scale * (pair.b @ pair.a))
            trainable = jax.tree_util.tree_unflatten(treedef, new_ad)
            resid = jax.tree_util.tree_unflatten(treedef, resid)
            frozen = jax.tree_util.tree_map(
                lambda p, r: p if r is None else p + r.astype(p.dtype),
                frozen, resid, is_leaf=lambda x: x is None)
            return trainable, frozen
        raise ValueError(s)

    def _fresh_adapters(self, round_idx):
        key = jax.random.PRNGKey(self.cfg.seed + 1000 + round_idx)
        return lora_lib.tree_lora_init(key, self.base_params, self.target_fn,
                                       self.cfg.rank)

    # -------------------------------------------------------------- 𝒮 -------
    def _sync_uplink(self, stacked_opt_states):
        """The 𝒮 input payload of a round: (projected-ṽ tree, basis tree)
        extracted from the client-stacked optimizer states — O(C·r·dim),
        the factored uplink, never the full optimizer state."""
        g_stack = gal.galore_state_of(stacked_opt_states)
        return (gal.extract_projected_v(g_stack),    # leaves (K, ., r)
                gal.extract_bases(g_stack))          # leaves (K, dim, r)

    def _slim_uplink(self, stacked_opt_states):
        """The shared-basis 𝒮 input payload — the projected-ṽ tree alone.
        This is what a pipelined scan carries between rounds: past the
        (possibly heterogeneous) adaptive round 0 every client holds the
        identical seeded basis, so the per-client basis stacks contribute
        nothing to 𝒮 and carrying them through the scan boundary is pure
        copy traffic. Shapes ride via :meth:`_basis_template`."""
        return gal.extract_projected_v(gal.galore_state_of(stacked_opt_states))

    def _slim_reduces_in_body(self) -> bool:
        """Whether the pipelined payload is the already-reduced synced tree.

        For the shared-basis weighted-mean protocols — 'avg', and 'avg_svd',
        whose rank-r re-projection is the identity on rank-≤r lifts — the
        whole 𝒮 is one fused ``einsum('k,k...->...')``: there is no
        spectral tail worth deferring, and carrying the (C, ·, r)
        per-client stacks across the scan boundary just to average them
        later is pure carry traffic (≈1 ms/round at C=512). So those
        protocols sync fully in-body (including the adaptive round-0
        hetero cond, exactly as the sequential body does): the pending
        slot holds the same small synced tree the sequential carry does,
        and the drain is a passthrough — only the install is
        re-associated across the round boundary. Only 'ajive' — whose
        joint-basis estimate needs the full per-client score stacks —
        defers the slim uplink."""
        return self.spec.state_sync in ("avg", "avg_svd")

    @jax.named_scope("fed.sync")
    def _slim_payload(self, stacked_opt_states, w, round_idx,
                      exclude_zero: bool, robust: str = "none"):
        """The ``skip_sync`` pending payload for one round: the fully
        synced tree for the weighted-mean protocols (via the normal
        :meth:`_sync_states_pure` — its internal round-0 cond covers the
        heterogeneous adaptive case, so the pipelined body does exactly
        the sequential body's sync work and only the *install* crosses
        the round boundary), the per-client projected-ṽ stacks for ajive
        (see :meth:`_slim_reduces_in_body`)."""
        if self._slim_reduces_in_body():
            return self._sync_states_pure(stacked_opt_states, w, round_idx,
                                          exclude_zero, robust=robust)
        return self._slim_uplink(stacked_opt_states)

    def _basis_template(self):
        """Zero-filled single-client basis tree (leaves ``(dim, r)``) —
        the shape/rank donor for :meth:`_sync_pending`. Scan-invariant
        (closed over, never carried); values are never read."""
        if self._basis_template_tree is None:
            st = jax.eval_shape(lambda: self.tx.init(self.global_trainable))
            b = gal.extract_bases(gal.galore_state_of(st))
            self._basis_template_tree = jax.tree_util.tree_map(
                lambda x: None if x is None else jnp.zeros(x.shape, x.dtype),
                b, is_leaf=lambda x: x is None)
        return self._basis_template_tree

    @jax.named_scope("fed.sync")
    def _sync_pending(self, v_tree, w, exclude_zero: bool = False,
                      robust: str = "none"):
        """Drain one slim pending payload (see :meth:`_slim_payload`):
        passthrough for the weighted-mean protocols (fully synced
        in-body, any round), shared-basis factored 𝒮 on the carried
        projected-moment stacks for ajive — where it is only valid for
        rounds ≥ 1 of a scan: the adaptive round 0 (diverged bases)
        syncs inline in its own body into the carried slot."""
        if self._slim_reduces_in_body():
            return v_tree
        return self._sync_states_from_uplink(
            v_tree, self._basis_template(), w, None, exclude_zero,
            shared_only=True, robust=robust)

    def _sync_blocks(self, v_stack_tree, basis_tree, block_fn):
        """Map ``block_fn(v_stack, b_stack, side, rank)`` over the adapted
        blocks, shape-identical leaves as one vmapped program per bucket
        (`state_sync.map_sync_leaves`)."""
        vs, treedef = jax.tree_util.tree_flatten(v_stack_tree,
                                                 is_leaf=lambda x: x is None)
        bs = jax.tree_util.tree_leaves(basis_tree, is_leaf=lambda x: x is None)

        def leaf_fn(v_stack, b_stack):
            rank = b_stack.shape[-1]
            side = proj.RIGHT if v_stack.shape[-1] == rank else proj.LEFT
            return block_fn(v_stack, b_stack, side, rank)

        synced = sync_lib.map_sync_leaves(leaf_fn, vs, bs)
        return jax.tree_util.tree_unflatten(treedef, synced)

    @jax.named_scope("fed.sync")
    def _sync_states_pure(self, stacked_opt_states, w, round_idx,
                          exclude_zero: bool = False, robust: str = "none"):
        """Factored 𝒮 for the fused round: shared-basis rounds synchronize on
        the projected ṽ directly (no lift); the adaptive round-0 diverged-
        basis case runs the heterogeneous-basis factored sync (r×r transfer
        Grams) — the dense (K, m, n) per-client lift never executes. The
        round-0 branch is a ``lax.cond`` so one compiled program serves the
        whole scanned sweep. ``exclude_zero`` (the participation-masked
        round) drops zero-weight clients from the AJIVE joint basis."""
        if not self._method_syncs():
            return None
        v_tree, b_tree = self._sync_uplink(stacked_opt_states)
        return self._sync_states_from_uplink(v_tree, b_tree, w, round_idx,
                                             exclude_zero, robust=robust)

    @jax.named_scope("fed.sync")
    def _sync_states_from_uplink(self, v_stack_tree, basis_tree, w, round_idx,
                                 exclude_zero: bool = False,
                                 shared_only: bool = False,
                                 robust: str = "none"):
        """𝒮 on an extracted uplink payload (see :meth:`_sync_uplink`) —
        shared with the pipelined scan drivers, which sync the *previous*
        round's carried payload at the top of the next round's body.
        ``shared_only`` statically drops the adaptive round-0 hetero branch
        (callers guarantee round ≥ 1); ``basis_tree`` then only donates
        per-leaf rank/side shapes and may be a single-client template.
        ``robust`` (guarded rounds) swaps the weighted-mean reductions over
        the projected-moment stacks inside the sync protocols for the
        robust estimator (``'none'`` is exactly the plain path — bitwise)."""
        protocol = self.spec.state_sync
        round0_hetero_possible = (not shared_only
                                  and self.galore_cfg.adaptive_steps > 0
                                  and self.galore_cfg.refresh_mode != "random")

        def sync_block(v_stack, b_stack, side, rank):
            def shared(_):
                # Shared-basis invariant (the seeded-broadcast protocol keeps
                # every client on the identical round-k basis): synchronize
                # directly on the projected ṽ — no (K, m, n) lift. The result
                # stays on the round-k basis; manual_refresh applies the
                # next-round transfer at InitState.
                return sync_lib.sync_block_synced_factored(
                    protocol, v_stack, side, w, rank,
                    exclude_zero_weights=exclude_zero, robust=robust,
                    trim=self.cfg.robust_trim, iters=self.cfg.robust_iters,
                    tol=self.cfg.robust_tol)

            def hetero(_):
                return sync_lib.sync_block_hetero_factored(
                    protocol, v_stack, b_stack, side, w, rank,
                    exclude_zero_weights=exclude_zero, robust=robust,
                    trim=self.cfg.robust_trim, iters=self.cfg.robust_iters,
                    tol=self.cfg.robust_tol)

            if not round0_hetero_possible:
                return shared(None)
            return jax.lax.cond(round_idx == 0, hetero, shared, operand=None)

        return self._sync_blocks(v_stack_tree, basis_tree, sync_block)

    # ------------------------------------------------------------- helpers --
    def global_params(self) -> PyTree:
        if self.spec.trainable in ("dense", "galore"):
            return merge_dense(self.frozen, self.global_trainable)
        return merge_lora(self.frozen, self.global_trainable, self.cfg.lora_scale)

    def evaluate(self, batch) -> float:
        if self.eval_fn is None:
            return float(self.loss_fn(self.global_params(), batch))
        return float(self.eval_fn(self.global_params(), batch))

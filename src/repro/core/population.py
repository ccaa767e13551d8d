"""Planet-scale cohorts: population store, fault/adversary injection, stale
buffer, crash-resumable orchestration.

The compiled federated round trains a fixed C-client cohort; a real
federation samples that cohort each round from a large, mostly-offline
population with heterogeneous capacity — and some sampled clients drop out,
deliver their update rounds late, or upload corrupted state. This module
decouples the two worlds:

ParticipationConfig / sample_cohort
    Seeded per-round fault AND adversary injection: which population clients
    the round's C compiled slots hold, which drop (never contribute), which
    straggle (contribute ``delay`` rounds late), and which are corrupted
    this round (NaN shard / sign-flip / norm-scale attack —
    ``corrupt_rate``, realized as uplink multipliers by
    :func:`corruption_multipliers`). The plan for round k is a pure host
    function of ``(config, k)`` — identical whether rounds are driven one
    ``run_round`` at a time or as one ``lax.scan`` sweep, and across
    restarts; corruption draws come strictly AFTER the fault draws, so
    enabling adversaries never perturbs who drops or straggles. Every plan
    keeps ≥ 1 HONEST on-time participant (a round with zero trustworthy
    weight is undefined; ``corrupt_rate >= 1`` raises).

ClientStateStore
    Sticky per-client factored state for the whole virtual population: the
    rank-r accumulator rows ``R_i`` and projected-moment rows ṽ_i each
    client last produced, O(r(m+n)) per client — ~10⁵ cold clients fit in
    host memory, and least-recently-used shards spill to disk through
    ``checkpoint.io`` (whose atomic save + payload validation + non-finite
    rejection make a crash mid-spill recoverable: the shard falls back to
    its last complete spill, or to cold zeros — never to NaN rows).
    ``gather`` assembles a sampled cohort's rows into the round's (C, ·, r)
    stacked layout; ``scatter`` writes the round's donated buffer rows back
    under the population ids.

StalenessBuffer
    FedBuff-style bounded-staleness aggregation: a straggler's factored
    contribution (R_i rows + ṽ_i rows + birth basis + base scale) is masked
    out of its birth round and buffered; at its due round it merges into the
    global weights and the synced moments with a ``staleness_decay**delay``
    weight. ``capacity`` bounds the buffer: pushing onto a full buffer
    evicts (drops) the earliest-due entry. Delay-0 participation bypasses
    the buffer entirely — even at capacity — so ``max_staleness=0`` is
    *exactly* the synchronous round.

PopulationRunner — the round lifecycle is plan → quarantine → aggregate →
snapshot:
    1. **plan**: ``sample_cohort`` fixes the round's participants, faults,
       and adversary assignments (pure in (config, round)).
    2. **quarantine**: the fused round runs with the plan's participation
       mask and corruption multipliers; inside the compiled program the
       engine screens every factored contribution (non-finite + median-norm
       outlier tests) and folds failures into the zero-weight mask path —
       renormalized out of 𝒜, excluded from the AJIVE score Gram in 𝒮,
       stacks sanitized. Corrupted clients are also barred from scattering
       poisoned rows into the store. A drift/loss tripwire can additionally
       roll the federation back to the round-start state and replay with
       host-detected offenders force-quarantined (bounded retries, then
       degrade with a warning).
    3. **aggregate**: robust factored 𝒜 + exclusion-aware 𝒮 produce the new
       global state; due stale updates merged beforehand, stragglers
       buffered after.
    4. **snapshot**: on the configured cadence the FULL federation state —
       server weights, synced moments, client buffers, staleness-buffer
       entries, store rows, history — is written through ``checkpoint.io``'s
       atomic writer (``keep_last`` GC bounds disk); :meth:`PopulationRunner.
       restore` rebuilds a killed run from the latest snapshot with
       loss-curve parity to an uninterrupted run.

Bit-identity guarantees (each asserted in tests): a full-participation mask
short-circuits onto the unmasked compiled program; an all-honest cohort
through the guarded (quarantine/robust) program is bit-identical to the
unguarded round; ``max_staleness=0`` is bit-exactly the synchronous round;
and chunked ≡ unchunked cohort streaming — so every defense and scaling
layer is pay-for-what-you-use.

Drift observatory: :func:`moment_divergence` (weighted dispersion of the
per-client projected moments around the synced v̄ — the quantity 𝒮 is
supposed to keep bounded under partial participation) and
:func:`tree_rel_err` (relative Frobenius error between pytrees, used for
the stale-vs-fresh aggregation error). ``benchmarks/bench_participation.py``
and ``benchmarks/bench_state_mismatch.py`` share these implementations.
"""
from __future__ import annotations

import dataclasses
import json
import os
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import galore as gal
from . import projector as proj
from ..checkpoint import io as ckpt_io

PyTree = Any


# ------------------------------------------------------------ fault plans ---

@dataclasses.dataclass(frozen=True)
class ParticipationConfig:
    """Seeded cohort sampling + fault injection knobs.

    population       virtual population size M; each round samples C of M
                     clients without replacement (0 ⇒ M = C, every client
                     holds a permanent slot — sampling degenerates to the
                     identity and only the fault injection remains).
    dropout_rate     P(a sampled client drops this round) — dropped clients
                     keep their compiled slot but carry zero effective
                     weight and are excluded from the AJIVE joint basis.
    straggler_rate   P(a surviving client straggles): its contribution is
                     masked out of the birth round and lands ``delay``
                     rounds late through the staleness buffer.
    max_staleness    k: straggler delays are uniform on {1..k}. 0 disables
                     straggling entirely (delay-0 ≡ on-time participation,
                     bypassing the buffer — bit-exactly synchronous).
    staleness_decay  β: a delay-d stale update merges with weight β^d.
    stale_scale      server-side learning rate on the stale merge.
    seed             fault-injection seed, independent of the train seed.
    corrupt_rate     P(an on-time client uploads corrupted state this
                     round). Adversary draws come strictly after the fault
                     draws (enabling them never changes who drops or
                     straggles), only on-time clients are corrupted (a
                     dropped adversary contributes nothing; a straggling
                     one would be screened at merge), and every plan keeps
                     ≥ 1 honest on-time participant — ``corrupt_rate >= 1``
                     makes that impossible and raises.
    corrupt_modes    which attacks the adversary mixes, drawn uniformly per
                     corrupted client: 'nan' (non-finite shard), 'sign_flip'
                     (negated update), 'scale' (update × attack_scale).
    attack_scale     multiplier of the 'scale' norm attack.
    """
    population: int = 0
    dropout_rate: float = 0.0
    straggler_rate: float = 0.0
    max_staleness: int = 0
    staleness_decay: float = 0.5
    stale_scale: float = 1.0
    seed: int = 0
    corrupt_rate: float = 0.0
    corrupt_modes: tuple = ("nan", "sign_flip", "scale")
    attack_scale: float = 100.0


CORRUPT_MODES = ("nan", "sign_flip", "scale")


class CohortPlan(NamedTuple):
    """One round's participation plan (host numpy, fully deterministic).

    clients  (C,) int64 population ids occupying the compiled cohort slots
    mask     (C,) bool — True = on-time participant (contributes this round)
    delays   (C,) int64 — 0 on-time, d ∈ {1..k} straggler (lands d rounds
             late), -1 dropped (never contributes)
    corrupt  (C,) int64 adversary assignment — 0 honest, j ≥ 1 the 1-based
             index into ``pcfg.corrupt_modes`` (None on hand-built plans:
             treated as all-honest)
    """
    round_idx: int
    clients: np.ndarray
    mask: np.ndarray
    delays: np.ndarray
    corrupt: Optional[np.ndarray] = None


def sample_cohort(pcfg: ParticipationConfig, cohort: int, round_idx: int,
                  population: Optional[int] = None) -> CohortPlan:
    """The round's cohort + fault plan as a pure function of (config, round).

    Deterministic in ``(pcfg.seed, round_idx)`` only — NOT in call order —
    so per-round drivers and scan-over-rounds drivers (and restarts) see
    identical plans. Draw order is fixed (sample → dropout → straggle →
    delays → corruption) so disabling a downstream knob never perturbs an
    upstream draw: ``max_staleness=0`` yields the same drops as
    ``straggler_rate=0``, and ``corrupt_rate=0`` yields the same
    clients/mask/delays as any positive rate.
    """
    pop = population if population is not None else (pcfg.population or cohort)
    if pop < cohort:
        raise ValueError(f"population {pop} < cohort {cohort}")
    rng = np.random.default_rng([pcfg.seed, round_idx])
    if pop == cohort:
        ids = np.arange(cohort, dtype=np.int64)
    else:
        ids = np.sort(rng.choice(pop, size=cohort,
                                 replace=False)).astype(np.int64)
    drop_u = rng.random(cohort)
    strag_u = rng.random(cohort)
    dropped = drop_u < pcfg.dropout_rate
    straggling = (~dropped) & (strag_u < pcfg.straggler_rate)
    if pcfg.max_staleness <= 0:
        straggling[:] = False          # delay-0 ≡ on-time: no buffering
    delays = np.zeros(cohort, dtype=np.int64)
    delays[dropped] = -1
    if straggling.any():
        delays[straggling] = rng.integers(1, pcfg.max_staleness + 1,
                                          size=int(straggling.sum()))
    if not (delays == 0).any():
        # A round needs ≥ 1 on-time participant: promote one deterministic
        # victim (the first faulted slot) back to on-time.
        delays[0] = 0
    mask = delays == 0
    # Adversary assignment — drawn strictly after the fault plan so the
    # clients/mask/delays above are invariant in corrupt_rate. Only on-time
    # clients are corruptible: a dropped adversary contributes nothing, and
    # corrupting a straggler would merely be screened at its stale merge.
    corrupt = np.zeros(cohort, dtype=np.int64)
    if pcfg.corrupt_rate > 0.0:
        for m in pcfg.corrupt_modes:
            if m not in CORRUPT_MODES:
                raise ValueError(f"corrupt mode {m!r} not in "
                                 f"{CORRUPT_MODES}")
        if not pcfg.corrupt_modes:
            raise ValueError("corrupt_rate > 0 needs >= 1 corrupt mode")
        corrupt_u = rng.random(cohort)
        bad = mask & (corrupt_u < pcfg.corrupt_rate)
        if bad.any():
            corrupt[bad] = rng.integers(1, len(pcfg.corrupt_modes) + 1,
                                        size=int(bad.sum()))
        if not (mask & (corrupt == 0)).any():
            # The honest counterpart of the on-time guarantee: quarantine
            # will (correctly) zero every corrupted contribution, so a
            # fully-adversarial on-time set would leave the round without
            # trustworthy weight. Pardon one deterministic victim — unless
            # the config makes honesty impossible.
            if pcfg.corrupt_rate >= 1.0:
                raise ValueError(
                    "corrupt_rate >= 1 leaves no honest on-time "
                    "participant in any round — quarantine + dropout must "
                    "leave at least one trustworthy client")
            corrupt[int(np.nonzero(mask)[0][0])] = 0
    return CohortPlan(round_idx=int(round_idx), clients=ids, mask=mask,
                      delays=delays, corrupt=corrupt)


def corruption_multipliers(plan: CohortPlan,
                           pcfg: ParticipationConfig) -> Optional[np.ndarray]:
    """Realize a plan's adversary assignments as the (C,) float32 per-client
    uplink multipliers the guarded round injects after the local phase
    (``FedEngine.run_round(attack=)``): 1.0 honest, NaN corrupted shard,
    -1.0 sign flip, ``attack_scale`` norm attack. None when the plan has no
    adversaries (the engine then never leaves the unguarded/un-attacked
    dispatch on its own)."""
    if plan.corrupt is None or not (plan.corrupt != 0).any():
        return None
    value = {"nan": np.float32(np.nan), "sign_flip": np.float32(-1.0),
             "scale": np.float32(pcfg.attack_scale)}
    mult = np.ones(plan.corrupt.shape[0], np.float32)
    for i in np.nonzero(plan.corrupt)[0]:
        mult[i] = value[pcfg.corrupt_modes[int(plan.corrupt[i]) - 1]]
    return mult


def corruption_schedule(pcfg: ParticipationConfig, cohort: int,
                        rounds: int, start_round: int = 0,
                        population: Optional[int] = None) -> list:
    """The seeded K-round attack schedule: one
    :func:`corruption_multipliers` entry per round (None for honest
    rounds), drawn from the same deterministic (seed, round) plans the
    participation layer uses. This is the shared attack operand source for
    engine AND runtime drivers — both sides of an attack-parity grid feed
    identical multipliers into ``run_round(attack=)``, so any divergence is
    the round program's, not the adversary's."""
    return [corruption_multipliers(
                sample_cohort(pcfg, cohort, start_round + k, population),
                pcfg)
            for k in range(int(rounds))]


# ------------------------------------------------------ client-state store --

def _flatten_with_keys(tree: PyTree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree_util.tree_structure(tree)
    keys = ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path) for path, _ in leaves]
    return keys, [leaf for _, leaf in leaves], treedef


class ClientStateStore:
    """Host-side sticky state for a virtual client population.

    Rows are stored in contiguous per-shard numpy arrays (``shard_size``
    clients per shard); cold shards spill to ``directory`` through the
    atomic ``checkpoint.io`` writer and reload on demand, so the resident
    set is ``max_resident_shards`` regardless of population size. A client
    that has never been scattered reads back as zeros (cold).

    ``template`` is a pytree of per-client leaves (no leading client axis);
    gather/scatter speak (len(ids), ·) stacked trees of the same structure —
    the round's donated-buffer layout.
    """

    def __init__(self, n_clients: int, template: PyTree,
                 directory: Optional[str] = None, shard_size: int = 1024,
                 max_resident_shards: Optional[int] = None):
        self.n_clients = int(n_clients)
        self.shard_size = int(shard_size)
        self.directory = directory
        self.n_shards = -(-self.n_clients // self.shard_size)
        if max_resident_shards is None:
            max_resident_shards = 64 if directory else self.n_shards
        if directory is None and max_resident_shards < self.n_shards:
            raise ValueError("spill requires a directory: "
                             f"{self.n_shards} shards > resident cap "
                             f"{max_resident_shards}")
        self.max_resident = max(1, int(max_resident_shards))
        keys, leaves, self._treedef = _flatten_with_keys(template)
        self._keys = keys
        self._specs = [(tuple(np.shape(x)), np.asarray(x).dtype if not
                        hasattr(x, "dtype") else np.dtype(x.dtype))
                       for x in leaves]
        # LRU resident set: shard idx -> list of (rows_in_shard, *leaf) arrays
        self._resident: "OrderedDict[int, list]" = OrderedDict()
        self._dirty: set = set()
        self.last_round = np.full(self.n_clients, -1, dtype=np.int64)
        self.spills = 0
        self.loads = 0

    # -- shard management --
    def _shard_rows(self, shard: int) -> int:
        lo = shard * self.shard_size
        return min(self.shard_size, self.n_clients - lo)

    def _zero_shard(self, shard: int) -> list:
        rows = self._shard_rows(shard)
        return [np.zeros((rows,) + shape, dtype) for shape, dtype
                in self._specs]

    def _shard_template(self, shard: int) -> list:
        return self._zero_shard(shard)

    def _ensure_resident(self, shard: int) -> list:
        if shard in self._resident:
            self._resident.move_to_end(shard)
            return self._resident[shard]
        data = None
        if self.directory is not None:
            try:
                restored = ckpt_io.restore(self.directory, shard,
                                           self._shard_template(shard),
                                           name="clients")
                # np.array (copy): restore hands back device arrays whose
                # numpy views are read-only, and shard rows must be writable
                data = [np.array(x) for x in restored]
                self.loads += 1
            except (FileNotFoundError, ValueError):
                # Never spilled, a spill cut short mid-write, or a payload
                # carrying non-finite rows (restore's rejection): the
                # atomic writer guarantees nothing half-written sits under
                # the final name, so "missing/invalid/poisoned" cleanly
                # means "cold" — NaN rows never round-trip into the store.
                data = None
        if data is None:
            data = self._zero_shard(shard)
        self._resident[shard] = data
        self._evict()
        return data

    def _evict(self):
        while len(self._resident) > self.max_resident:
            shard, data = self._resident.popitem(last=False)
            if shard in self._dirty:
                self._spill(shard, data)

    def _spill(self, shard: int, data: list):
        if self.directory is None:
            raise RuntimeError("eviction without a spill directory")
        ckpt_io.save(self.directory, shard, data, name="clients")
        self._dirty.discard(shard)
        self.spills += 1

    def flush(self):
        """Spill every dirty resident shard (atomic per shard)."""
        if self.directory is None:
            return
        for shard in sorted(self._dirty & set(self._resident)):
            self._spill(shard, self._resident[shard])

    # -- row access --
    def gather(self, ids: np.ndarray) -> PyTree:
        """Rows for ``ids`` as a stacked (len(ids), ·) pytree (zeros for
        cold clients) — the round's client-buffer layout."""
        ids = np.asarray(ids, np.int64)
        outs = [np.empty((len(ids),) + shape, dtype)
                for shape, dtype in self._specs]
        shards = ids // self.shard_size
        for shard in np.unique(shards):
            sel = np.nonzero(shards == shard)[0]
            rows = ids[sel] - shard * self.shard_size
            data = self._ensure_resident(int(shard))
            for o, d in zip(outs, data):
                o[sel] = d[rows]
        return jax.tree_util.tree_unflatten(self._treedef, outs)

    def scatter(self, ids: np.ndarray, rows: PyTree,
                round_idx: Optional[int] = None):
        """Write stacked rows back under population ids (marks shards
        dirty; they spill lazily on eviction or ``flush``)."""
        ids = np.asarray(ids, np.int64)
        _, leaves, _ = _flatten_with_keys(rows)
        if len(leaves) != len(self._specs):
            raise ValueError("scatter tree structure != store template")
        leaves = [np.asarray(x) for x in leaves]
        shards = ids // self.shard_size
        for shard in np.unique(shards):
            sel = np.nonzero(shards == shard)[0]
            rel = ids[sel] - shard * self.shard_size
            data = self._ensure_resident(int(shard))
            for d, leaf in zip(data, leaves):
                d[rel] = leaf[sel]
            self._dirty.add(int(shard))
        if round_idx is not None:
            self.last_round[ids] = int(round_idx)

    def resident_bytes(self) -> int:
        return sum(a.nbytes for data in self._resident.values() for a in data)


# ------------------------------------------------------- staleness buffer ---

class StaleEntry(NamedTuple):
    """One straggler's buffered factored contribution.

    deltas  per-leaf client update: rank-r accumulator rows R_i (factored
            GaLore clients) or dense trainable deltas vs the birth-round
            global (dense/LoRA clients)
    bases   per-leaf (dim, r) birth-round basis (None leaves for dense)
    v_rows  per-leaf projected-moment rows ṽ_i (None for non-sync methods)
    """
    client_id: int
    birth_round: int
    due_round: int
    weight: float          # cohort sample weight at birth
    decay: float           # staleness_decay**delay * stale_scale
    base_scale: float      # (1-ηλ)^T at birth
    deltas: PyTree
    bases: Optional[PyTree]
    v_rows: Optional[PyTree]


class StalenessBuffer:
    """FedBuff-style bounded buffer: entries keyed by due round; by
    construction no entry lives longer than ``max_staleness`` rounds.

    ``capacity`` (None = unbounded) additionally caps the number of buffered
    entries: pushing onto a full buffer first evicts the entry with the
    earliest due round (FIFO among ties — the entry closest to merging,
    i.e. the least information lost relative to its decay weight), DROPS it
    (counted in ``evictions``, returned to the caller for observability),
    and then admits the new entry. Only stragglers ever reach ``push`` —
    delay-0 participation bypasses the buffer entirely, so a full buffer
    never affects on-time clients."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is not None and int(capacity) < 1:
            raise ValueError(f"capacity must be >= 1 or None, got {capacity}")
        self.capacity = None if capacity is None else int(capacity)
        self._entries: List[StaleEntry] = []
        self.evictions = 0

    def push(self, entry: StaleEntry) -> Optional[StaleEntry]:
        evicted = None
        if (self.capacity is not None
                and len(self._entries) >= self.capacity):
            idx = min(range(len(self._entries)),
                      key=lambda i: (self._entries[i].due_round, i))
            evicted = self._entries.pop(idx)
            self.evictions += 1
        self._entries.append(entry)
        return evicted

    def pop_due(self, round_idx: int) -> List[StaleEntry]:
        due = [e for e in self._entries if e.due_round <= round_idx]
        self._entries = [e for e in self._entries if e.due_round > round_idx]
        return due

    def __len__(self):
        return len(self._entries)

    @property
    def pending_rounds(self) -> List[int]:
        return sorted({e.due_round for e in self._entries})


# ------------------------------------------------------ drift observatory ---

def moment_divergence(v_rows: PyTree, v_bar: PyTree,
                      weights: Optional[np.ndarray] = None) -> float:
    """Weighted relative dispersion of per-client projected moments around
    the synced v̄: sqrt(Σ_i w_i ‖ṽ_i − v̄‖²_F) / (‖v̄‖_F + ε), summed over
    adapted blocks. This is the drift 𝒮 is meant to absorb — the shared
    metric of the participation bench and ``bench_state_mismatch``."""
    num, den = 0.0, 0.0
    rows = jax.tree_util.tree_leaves(v_rows, is_leaf=lambda x: x is None)
    bars = jax.tree_util.tree_leaves(v_bar, is_leaf=lambda x: x is None)
    w = None
    for r_leaf, b_leaf in zip(rows, bars):
        if r_leaf is None or b_leaf is None:
            continue
        r_np = np.asarray(r_leaf, np.float64)
        b_np = np.asarray(b_leaf, np.float64)
        if w is None:
            w = (np.full(r_np.shape[0], 1.0 / r_np.shape[0])
                 if weights is None else
                 np.asarray(weights, np.float64) /
                 max(float(np.sum(weights)), 1e-30))
        diff = (r_np - b_np[None]).reshape(r_np.shape[0], -1)
        num += float(w @ np.sum(diff * diff, axis=1))
        den += float(np.sum(b_np ** 2))
    return float(np.sqrt(num) / (np.sqrt(den) + 1e-12))


def tree_rel_err(tree_a: PyTree, tree_b: PyTree) -> float:
    """Relative Frobenius error ‖a − b‖_F / (‖b‖_F + ε) across all leaves —
    the stale-vs-fresh aggregation error metric."""
    num, den = 0.0, 0.0
    la = jax.tree_util.tree_leaves(tree_a, is_leaf=lambda x: x is None)
    lb = jax.tree_util.tree_leaves(tree_b, is_leaf=lambda x: x is None)
    for a, b in zip(la, lb):
        if a is None or b is None:
            continue
        a_np = np.asarray(a, np.float64)
        b_np = np.asarray(b, np.float64)
        num += float(np.sum((a_np - b_np) ** 2))
        den += float(np.sum(b_np ** 2))
    return float(np.sqrt(num) / (np.sqrt(den) + 1e-12))


# ------------------------------------------------------------- the runner ---

def _moment_leaf_side(delta_leaf, basis_leaf) -> str:
    """Projected-buffer side convention (matches ``fed._aggregate_factored``):
    right buffers are (..., m, r) with an (..., n, r) basis — trailing dims
    agree; left buffers are (..., r, n) with (..., m, r)."""
    return (proj.RIGHT if delta_leaf.shape[-1] == basis_leaf.shape[-1]
            else proj.LEFT)


class PopulationRunner:
    """Drives ``FedEngine`` rounds against a virtual population.

    Per round: sample the cohort plan → merge due stale updates into the
    global state → gather the cohort's sticky rows → run the masked fused
    round (compiled shapes untouched) → harvest the round's retained client
    buffers → push stragglers into the staleness buffer → scatter rows back
    to the store → record drift metrics.

    ``batches_for(ids, round_idx)`` supplies the cohort's local data with
    leading (C, T, ...) axes (e.g. ``lambda ids, r:
    batcher.round_batches(T, clients=list(ids))``).

    The harvest reads the engine's retained post-round client buffers.

    Defense-in-depth layers (all off by default):

    * **Adversary injection** — when the participation config draws
      corrupted clients, their uplink is perturbed *inside* the compiled
      round via the engine's attack operand (``corruption_multipliers``),
      and their rows are excluded from the sticky-row scatter.
    * **Snapshots** — ``snapshot_dir`` + ``snapshot_every=k`` persist the
      full federation state (global, retained client buffers, staleness
      buffer, store round-stamps, history) every k rounds through the
      atomic checkpoint writer, retaining ``snapshot_keep`` snapshots.
    * **Tripwire** — ``drift_tripwire`` / ``loss_tripwire`` thresholds
      arm a host-side guard: when a round's ``moment_divergence`` or
      ``mean_final_loss`` spikes past the threshold (or goes non-finite),
      the runner rolls the federation back to the captured round-start
      state and replays the round with the offending clients quarantined
      (host-side screen of the harvested uplink), for at most
      ``tripwire_retries`` replays before degrading with a warning.
    """

    def __init__(self, engine, batches_for: Callable[[np.ndarray, int], PyTree],
                 cohort: int, pcfg: Optional[ParticipationConfig] = None,
                 store_dir: Optional[str] = None, shard_size: int = 1024,
                 max_resident_shards: Optional[int] = None,
                 buffer_capacity: Optional[int] = None,
                 snapshot_dir: Optional[str] = None, snapshot_every: int = 0,
                 snapshot_keep: int = 3, drift_tripwire: float = 0.0,
                 loss_tripwire: float = 0.0, tripwire_retries: int = 1):
        self.engine = engine
        self.batches_for = batches_for
        self.cohort = int(cohort)
        self.pcfg = pcfg or engine.cfg.participation or ParticipationConfig()
        self.population = self.pcfg.population or self.cohort
        self.store = ClientStateStore(
            self.population, self._row_template(), directory=store_dir,
            shard_size=shard_size, max_resident_shards=max_resident_shards)
        self.buffer = StalenessBuffer(capacity=buffer_capacity)
        self.history: List[Dict[str, float]] = []
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = int(snapshot_every)
        self.snapshot_keep = int(snapshot_keep)
        self.drift_tripwire = float(drift_tripwire)
        self.loss_tripwire = float(loss_tripwire)
        self.tripwire_retries = int(tripwire_retries)
        self._last_harvest: Optional[Dict[str, PyTree]] = None

    # -- templates / layout --
    def _galore_shapes(self):
        eng = self.engine
        st = jax.eval_shape(lambda: eng.tx.init(eng.global_trainable))
        g = gal.galore_state_of(st)
        v_tree = gal.extract_projected_v(g)
        return jax.tree_util.tree_map(
            lambda x: None if x is None else np.zeros(x.shape, np.float32),
            v_tree, is_leaf=lambda x: x is None)

    def _row_template(self) -> PyTree:
        """Per-client sticky row: factored accumulator + projected moments
        (GaLore clients), or the dense trainable delta (LoRA/dense
        clients)."""
        eng = self.engine
        if eng._factored:
            moments = self._galore_shapes()
            return {"delta": moments, "v": moments}
        tmpl = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, np.float32), eng.global_trainable)
        row = {"delta": tmpl}
        if eng.spec.optimizer == "galore_adamw":
            row["v"] = self._galore_shapes()
        return row

    def _base_scale(self) -> float:
        """(1-ηλ)^T — the factored round's decoupled-weight-decay scalar,
        identical across clients under the constant engine lr."""
        c = self.engine.cfg
        return float((1.0 - c.lr * c.weight_decay) ** c.local_steps)

    # -- harvest: slice the engine's retained post-round buffers host-side --
    def _harvest(self) -> Dict[str, PyTree]:
        eng = self.engine
        out: Dict[str, PyTree] = {}
        if eng._factored:
            out["delta"] = jax.tree_util.tree_map(np.asarray,
                                                  eng._client_state)
        else:
            out["trainable"] = jax.tree_util.tree_map(np.asarray,
                                                      eng._client_state)
        if eng.spec.optimizer == "galore_adamw":
            g = gal.galore_state_of(eng._client_opt)
            to_np = lambda t: jax.tree_util.tree_map(
                lambda x: None if x is None else np.asarray(x), t,
                is_leaf=lambda x: x is None)
            out["v"] = to_np(gal.extract_projected_v(g))
            out["bases"] = to_np(gal.extract_bases(g))
        return out

    @staticmethod
    def _rows(tree: Optional[PyTree], i: int) -> Optional[PyTree]:
        if tree is None:
            return None
        return jax.tree_util.tree_map(
            lambda x: None if x is None else x[i], tree,
            is_leaf=lambda x: x is None)

    # -- stale merge --
    def _merge_due(self, round_idx: int) -> Dict[str, float]:
        """Apply due stale contributions to the engine's global state
        (FedBuff server step), BEFORE the round runs.

        Weights: ``W ← W·(1 + Σ_j α_j (s_j − 1)) + Σ_j α_j·lift(R_j, B_j)``
        for factored clients (the decay term applied against the *current*
        base — exact when weight_decay=0, the documented FedBuff-style
        approximation otherwise), or ``W ← W + Σ_j α_j Δ_j`` for dense/LoRA
        deltas, with α_j = weight_j · decay_j.

        Moments: v̄ ← (1−ρ)·v̄ + ρ·(Σ α_j ṽ_j→now / Σα), ρ = Σα/(1+Σα), each
        stale ṽ re-based from its birth basis onto the current basis via the
        r×r transfer, clamped ≥ 0 (second moments).
        """
        due = self.buffer.pop_due(round_idx)
        if not due:
            return {"stale_merged": 0, "stale_weight_err": 0.0,
                    "stale_moment_div": 0.0}
        eng = self.engine
        tmap = jax.tree_util.tree_map
        g_old = eng.global_trainable

        # -- weights: fold each due entry into the global trainable. Every
        # tree here (trainable, factored deltas, bases) shares one treedef —
        # they are all tree_maps over the trainable tree — so structural
        # Nones (frozen leaves) align and tree_map skips them uniformly.
        g_acc = tmap(lambda x: np.asarray(x, np.float64), g_old)
        for e in due:
            alpha = e.weight * e.decay
            if e.bases is not None:
                lifted = tmap(
                    lambda d, b: np.asarray(proj.project_back(
                        jnp.asarray(d, jnp.float32),
                        jnp.asarray(b, jnp.float32),
                        _moment_leaf_side(d, b)), np.float64),
                    e.deltas, e.bases)
                g_acc = tmap(
                    lambda acc, l, a=alpha, s=e.base_scale:
                        acc + a * (s - 1.0) * acc + a * l,
                    g_acc, lifted)
            else:
                g_acc = tmap(
                    lambda acc, d, a=alpha:
                        acc + a * np.asarray(d, np.float64),
                    g_acc, e.deltas)
        g_new = tmap(lambda acc, x: jnp.asarray(acc.astype(np.float32),
                                                x.dtype), g_acc, g_old)
        weight_err = tree_rel_err(g_new, g_old)
        eng.global_trainable = g_new

        # -- moments: reproject each stale ṽ birth→current basis, decay-merge.
        stale_div = 0.0
        v_entries = [(e, e.weight * e.decay) for e in due
                     if e.v_rows is not None]
        if eng.synced_v is not None and v_entries:
            cur_bases = gal.extract_bases(
                gal.galore_state_of(eng._client_opt))
            cur0 = tmap(lambda b: np.asarray(b[0]), cur_bases)
            a_sum = sum(a for _, a in v_entries)
            rho = a_sum / (1.0 + a_sum)
            moved_list = []
            acc = None
            for e, alpha in v_entries:
                moved = tmap(
                    lambda v, b, c: np.asarray(proj.reproject(
                        jnp.asarray(v, jnp.float32),
                        jnp.asarray(b, jnp.float32),
                        jnp.asarray(c, jnp.float32),
                        _moment_leaf_side(v, b)), np.float64),
                    e.v_rows, e.bases, cur0)
                moved_list.append(moved)
                acc = (tmap(lambda m, a=alpha: a * m, moved) if acc is None
                       else tmap(lambda s, m, a=alpha: s + a * m, acc, moved))
            v_bar_old = tmap(lambda v: np.asarray(v, np.float64),
                             eng.synced_v)
            eng.synced_v = tmap(
                lambda vb, s: jnp.asarray(np.maximum(
                    (1.0 - rho) * vb + rho * (s / a_sum),
                    0.0).astype(np.float32)),
                v_bar_old, acc)
            stale_div = moment_divergence(
                tmap(lambda *ms: np.stack(ms), *moved_list), v_bar_old,
                weights=np.asarray([a for _, a in v_entries]))
        return {"stale_merged": len(due), "stale_weight_err": weight_err,
                "stale_moment_div": stale_div}

    # -- one population round --
    def run_round(self, weights: Optional[np.ndarray] = None
                  ) -> Dict[str, Any]:
        eng = self.engine
        plan = sample_cohort(self.pcfg, self.cohort, eng.round_idx,
                             self.population)
        tripwire = self.drift_tripwire > 0.0 or self.loss_tripwire > 0.0
        guard = self._capture(plan) if tripwire else None
        record = self._execute_round(plan, weights)

        replays = 0
        quarantined = np.zeros(self.cohort, bool)
        while tripwire and self._tripped(record):
            offenders = (self._offending_clients()
                         & plan.mask & ~quarantined)
            new_q = quarantined | offenders
            still_live = (plan.mask & ~new_q).any()
            if (replays >= self.tripwire_retries or not offenders.any()
                    or not still_live):
                warnings.warn(
                    "tripwire: round %d still exceeds thresholds after %d "
                    "replay(s) (drift=%.3g loss=%.3g); degrading — keeping "
                    "the tripped round's result"
                    % (record["round"], replays,
                       record["moment_divergence"],
                       record["mean_final_loss"]))
                break
            quarantined = new_q
            self._rollback(guard)
            # Quarantined clients drop out entirely: masked, no delay slot,
            # and their corruption code cleared so the attack operand does
            # not re-inject NaN into their (now zero-weight) rows.
            replay_plan = plan._replace(
                mask=plan.mask & ~quarantined,
                delays=np.where(quarantined, -1, plan.delays),
                corrupt=(None if plan.corrupt is None else
                         np.where(quarantined, 0, plan.corrupt)))
            record = self._execute_round(replay_plan, weights)
            replays += 1
        if tripwire:
            extra = {"tripwire_replays": replays,
                     "tripwire_quarantined": int(quarantined.sum())}
            self.history[-1].update(extra)
            record.update(extra)

        if (self.snapshot_dir is not None and self.snapshot_every > 0
                and eng.round_idx % self.snapshot_every == 0):
            self.snapshot()
        return record

    def _execute_round(self, plan: CohortPlan,
                       weights: Optional[np.ndarray]) -> Dict[str, Any]:
        eng = self.engine
        t = eng.round_idx
        stale_metrics = self._merge_due(t)
        gathered = self.store.gather(plan.clients)   # sticky rows (obs/warm)
        batches = self.batches_for(plan.clients, t)
        prev_global = None
        if not eng._factored:
            # Dense/LoRA clients report stale deltas against their BIRTH
            # round's global (the model they trained from) — capture it
            # before the round aggregates (global_trainable is not donated,
            # so this is a live reference, not a copy race).
            prev_global = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32), eng.global_trainable)
        attack = corruption_multipliers(plan, self.pcfg)
        metrics = eng.run_round(batches, weights=weights, mask=plan.mask,
                                attack=attack)

        harvest = self._harvest()
        self._last_harvest = harvest
        scale = self._base_scale()
        w_norm = np.asarray(eng._normalize_weights(weights, self.cohort))

        # Stragglers: buffer their factored contribution for the due round.
        # (Corruption is drawn on-time-only, so every straggler is honest.)
        evict0 = self.buffer.evictions
        for i in np.nonzero(plan.delays > 0)[0]:
            delay = int(plan.delays[i])
            if eng._factored:
                deltas = self._rows(harvest["delta"], i)
                bases = self._rows(harvest["bases"], i)
            else:
                tr_i = self._rows(harvest["trainable"], i)
                deltas = jax.tree_util.tree_map(
                    lambda a, b: np.asarray(a, np.float32) - b,
                    tr_i, prev_global)
                bases = None
            self.buffer.push(StaleEntry(
                client_id=int(plan.clients[i]), birth_round=t,
                due_round=t + delay, weight=float(w_norm[i]),
                decay=float(self.pcfg.staleness_decay ** delay
                            * self.pcfg.stale_scale),
                base_scale=scale, deltas=deltas, bases=bases,
                v_rows=self._rows(harvest.get("v"), i)))

        # Scatter: participants + stragglers persist their new sticky rows;
        # dropped clients keep their previous (possibly cold) rows, and so
        # do corrupted clients — their harvested rows carry the attacked (or
        # quarantine-zeroed) uplink, which must not poison the store.
        live = plan.delays >= 0
        if plan.corrupt is not None:
            live = live & (plan.corrupt == 0)
        if live.any():
            rows: Dict[str, PyTree] = {}
            if eng._factored:
                rows["delta"] = jax.tree_util.tree_map(
                    lambda x: x[live], harvest["delta"])
                rows["v"] = jax.tree_util.tree_map(
                    lambda x: None if x is None else x[live], harvest["v"],
                    is_leaf=lambda x: x is None)
            else:
                rows["delta"] = jax.tree_util.tree_map(
                    lambda a, b: np.asarray(a, np.float32)[live] - b[None],
                    harvest["trainable"], prev_global)
                if "v" in harvest:
                    rows["v"] = jax.tree_util.tree_map(
                        lambda x: None if x is None else x[live],
                        harvest["v"], is_leaf=lambda x: x is None)
            self.store.scatter(plan.clients[live], rows, round_idx=t)

        # Drift observatory: dispersion of on-time clients' end-of-round
        # moments around the freshly synced v̄.
        drift = 0.0
        if eng.synced_v is not None and "v" in harvest:
            on = plan.mask
            drift = moment_divergence(
                jax.tree_util.tree_map(
                    lambda x: None if x is None else x[on], harvest["v"],
                    is_leaf=lambda x: x is None),
                eng.synced_v, weights=w_norm[on])

        record = {
            "round": int(t),
            "participants": int(plan.mask.sum()),
            "dropped": int((plan.delays < 0).sum()),
            "straggling": int((plan.delays > 0).sum()),
            "buffered": len(self.buffer),
            "moment_divergence": drift,
            "mean_final_loss": float(np.asarray(
                metrics["local_loss"])[plan.mask, -1].mean()),
            "corrupted": (0 if plan.corrupt is None
                          else int((plan.corrupt != 0).sum())),
            "stale_evicted": self.buffer.evictions - evict0,
            **stale_metrics,
        }
        self.history.append(record)
        record = dict(record)
        record["plan"] = plan
        record["gathered"] = gathered
        record["local_loss"] = metrics["local_loss"]
        return record

    # -- tripwire: capture / detect / rollback / screen --
    def _capture(self, plan: CohortPlan) -> Dict[str, Any]:
        """Round-start state for rollback. JAX arrays are immutable and the
        referenced engine buffers (global/frozen/synced) are never donated,
        so references suffice; host-side state is copied."""
        eng = self.engine
        cap = {"global": eng.global_trainable, "synced": eng.synced_v,
               "round_idx": eng.round_idx,
               "entries": list(self.buffer._entries),
               "evictions": self.buffer.evictions,
               "history_len": len(self.history),
               "clients": plan.clients.copy(),
               "rows": self.store.gather(plan.clients),
               "last_round": self.store.last_round.copy()}
        if eng._frozen_mutates():
            cap["frozen"] = eng.frozen
        return cap

    def _rollback(self, cap: Dict[str, Any]) -> None:
        eng = self.engine
        eng.global_trainable = cap["global"]
        eng.synced_v = cap["synced"]
        if "frozen" in cap:
            eng.frozen = cap["frozen"]
        eng.round_idx = cap["round_idx"]
        self.buffer._entries = list(cap["entries"])
        self.buffer.evictions = cap["evictions"]
        del self.history[cap["history_len"]:]
        self.store.scatter(cap["clients"], cap["rows"])
        self.store.last_round = cap["last_round"].copy()

    def _tripped(self, record: Dict[str, Any]) -> bool:
        loss = record["mean_final_loss"]
        drift = record["moment_divergence"]
        if not (np.isfinite(loss) and np.isfinite(drift)):
            return True
        if self.loss_tripwire > 0.0 and loss > self.loss_tripwire:
            return True
        return self.drift_tripwire > 0.0 and drift > self.drift_tripwire

    def _offending_clients(self) -> np.ndarray:
        """Host-side screen of the last harvested uplink, mirroring the
        in-round quarantine in float64: a client offends when any of its
        retained buffers are non-finite, or when its factored norm exceeds
        ``quarantine_zmax`` × the cohort median norm."""
        h = self._last_harvest
        if h is None:
            return np.zeros(self.cohort, bool)
        finite = np.ones(self.cohort, bool)
        sq = np.zeros(self.cohort)
        delta_tree = h["delta"] if "delta" in h else h["trainable"]
        for tree in (delta_tree, h.get("v")):
            if tree is None:
                continue
            for x in jax.tree_util.tree_leaves(
                    tree, is_leaf=lambda x: x is None):
                if x is None:
                    continue
                x2 = np.asarray(x, np.float64).reshape(self.cohort, -1)
                ok = np.isfinite(x2)
                finite &= ok.all(axis=1)
                x2 = np.where(ok, x2, 0.0)
                sq += (x2 * x2).sum(axis=1)
        norm = np.sqrt(sq)
        out = ~finite
        med = np.median(norm[finite]) if finite.any() else 0.0
        if med > 0.0:
            out |= norm > self.engine.cfg.quarantine_zmax * med
        return out

    # -- snapshots: crash-resumable federation state --
    def _entry_template(self) -> Dict[str, Optional[PyTree]]:
        """Per-entry restore template matching ``StaleEntry`` array trees."""
        eng = self.engine
        if eng._factored:
            moments = self._galore_shapes()
            st = jax.eval_shape(lambda: eng.tx.init(eng.global_trainable))
            b_tree = gal.extract_bases(gal.galore_state_of(st))
            bases = jax.tree_util.tree_map(
                lambda x: None if x is None else np.zeros(x.shape,
                                                          np.float32),
                b_tree, is_leaf=lambda x: x is None)
            return {"deltas": moments, "bases": bases, "v_rows": moments}
        deltas = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, np.float32), eng.global_trainable)
        row = {"deltas": deltas, "bases": None, "v_rows": None}
        if eng.spec.optimizer == "galore_adamw":
            row["v_rows"] = self._galore_shapes()
        return row

    def snapshot(self, step: Optional[int] = None) -> int:
        """Persist the full federation state atomically.

        Payload (npz, via :mod:`repro.checkpoint.io`): global trainable,
        retained per-client buffers (non-finite entries sanitized to 0 —
        they are rebuilt from the global at round start and must not trip
        the restore-side corruption check), staleness-buffer entry arrays,
        the store's round stamps, and synced_v/frozen when live. Scalar
        metadata (round index, history, entry bookkeeping) goes to a
        sibling ``fed_<step>.meta.json`` written with the same
        tmp+rename discipline. Retains ``snapshot_keep`` snapshots.
        """
        if self.snapshot_dir is None:
            raise ValueError("snapshot_dir is not configured")
        eng = self.engine
        step = int(eng.round_idx if step is None else step)
        self.store.flush()
        eng._ensure_client_buffers(self.cohort)
        clean = lambda t: jax.tree_util.tree_map(
            lambda x: None if x is None else np.nan_to_num(
                np.asarray(x), nan=0.0, posinf=0.0, neginf=0.0),
            t, is_leaf=lambda x: x is None)
        payload: Dict[str, Any] = {
            "global": eng.global_trainable,
            "client_state": clean(eng._client_state),
            "client_opt": clean(eng._client_opt),
            "last_round": self.store.last_round,
            "entries": [{"deltas": clean(e.deltas),
                         "bases": clean(e.bases),
                         "v_rows": clean(e.v_rows)}
                        for e in self.buffer._entries]}
        if eng.synced_v is not None:
            payload["synced_v"] = eng.synced_v
        if eng._frozen_mutates():
            payload["frozen"] = eng.frozen
        ckpt_io.save(self.snapshot_dir, step, payload, name="fed",
                     keep_last=self.snapshot_keep)
        meta = {"round_idx": int(eng.round_idx),
                "history": self.history,
                "has_synced_v": eng.synced_v is not None,
                "has_frozen": bool(eng._frozen_mutates()),
                "buffer_evictions": int(self.buffer.evictions),
                "entries": [{"client_id": int(e.client_id),
                             "birth_round": int(e.birth_round),
                             "due_round": int(e.due_round),
                             "weight": float(e.weight),
                             "decay": float(e.decay),
                             "base_scale": float(e.base_scale),
                             "has_bases": e.bases is not None,
                             "has_v": e.v_rows is not None}
                            for e in self.buffer._entries]}
        mpath = os.path.join(self.snapshot_dir,
                             "fed_%08d.meta.json" % step)
        tmp = mpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, mpath)
        return step

    def restore(self, step: Optional[int] = None) -> int:
        """Rebuild the federation from a snapshot (latest when ``step`` is
        None) — the crash-resume path: construct a fresh runner with the
        same config, then ``restore()``. The checkpoint reader rejects
        non-finite payloads, so a poisoned snapshot fails loudly here
        instead of silently resuming corrupted state."""
        if self.snapshot_dir is None:
            raise ValueError("snapshot_dir is not configured")
        if step is None:
            step = ckpt_io.latest_step(self.snapshot_dir, name="fed")
            if step is None:
                raise FileNotFoundError(
                    "no federation snapshot found in %r" % self.snapshot_dir)
        step = int(step)
        mpath = os.path.join(self.snapshot_dir,
                             "fed_%08d.meta.json" % step)
        with open(mpath) as f:
            meta = json.load(f)
        eng = self.engine
        eng._ensure_client_buffers(self.cohort)
        base_entry = self._entry_template()
        entry_templates = []
        for info in meta["entries"]:
            t = dict(base_entry)
            if not info["has_bases"]:
                t["bases"] = None
            if not info["has_v"]:
                t["v_rows"] = None
            entry_templates.append(t)
        template: Dict[str, Any] = {
            "global": eng.global_trainable,
            "client_state": eng._client_state,
            "client_opt": eng._client_opt,
            # int32 template: round stamps fit comfortably and jnp would
            # truncate int64 anyway under the default x64-off config.
            "last_round": self.store.last_round.astype(np.int32),
            "entries": entry_templates}
        if meta["has_synced_v"]:
            template["synced_v"] = (eng.synced_v if eng.synced_v is not None
                                    else eng._zero_synced_template())
        if meta["has_frozen"]:
            template["frozen"] = eng.frozen
        data = ckpt_io.restore(self.snapshot_dir, step, template, name="fed")
        eng.global_trainable = data["global"]
        eng._client_state = data["client_state"]
        eng._client_opt = data["client_opt"]
        eng.synced_v = data["synced_v"] if meta["has_synced_v"] else None
        if meta["has_frozen"]:
            eng.frozen = data["frozen"]
        eng.round_idx = int(meta["round_idx"])
        self.history = list(meta["history"])
        self.store.last_round = np.asarray(data["last_round"], np.int64)
        entries = []
        for info, trees in zip(meta["entries"], data["entries"]):
            entries.append(StaleEntry(
                client_id=int(info["client_id"]),
                birth_round=int(info["birth_round"]),
                due_round=int(info["due_round"]),
                weight=float(info["weight"]), decay=float(info["decay"]),
                base_scale=float(info["base_scale"]),
                deltas=trees["deltas"],
                bases=trees["bases"] if info["has_bases"] else None,
                v_rows=trees["v_rows"] if info["has_v"] else None))
        self.buffer._entries = entries
        self.buffer.evictions = int(meta.get("buffer_evictions", 0))
        self._last_harvest = None
        return step

    def run_rounds(self, k_rounds: int,
                   weights: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """K sequential population rounds (stale merges mutate the carry on
        the host between rounds, so the scanned driver cannot absorb them;
        dropout-only configs can use ``FedEngine.run_rounds(masks=...)``
        directly)."""
        out = None
        for _ in range(int(k_rounds)):
            out = self.run_round(weights=weights)
        self.store.flush()
        return {"history": self.history[-int(k_rounds):],
                "last": out}

"""Lock-in suite for the batched-bucket 𝒮 and the one-round pipelined scan.

Two independent equivalences, each against its reference:

* **Bucketed 𝒮 ≡ per-leaf 𝒮** (`state_sync.map_sync_leaves`): shape-bucketed
  vmapped sync must reproduce the per-leaf loop
  (`state_sync.map_sync_leaves_per_leaf`) for every protocol, both sides,
  stacked scan-block leaves, shared AND heterogeneous (transfer-Gram) bases
  and masked cohorts — in units, and on the engine's and the runtime's own
  uplinked states. On CPU the batched eigh is bit-identical, so tolerances
  are fp-noise tight.

* **Pipelined rounds ≡ sequential rounds**: the pipelined `run_rounds` scan
  (round k's 𝒮 deferred to the top of round k+1, post-scan drain) is a pure
  re-association of the sequential schedule — state-for-state the results
  of K successive `run_round` calls in both the engine (`core.fed`) and the
  sharded runtime (`fedsim.runtime`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projector as proj
from repro.core import state_sync as sync
from repro.core.fed import FedConfig, FedEngine

PROTOCOLS = ["avg", "avg_svd", "ajive"]
GALORE_METHODS = ["fedgalore", "fedgalore_minus", "fedgalore_avg",
                  "fedgalore_avg_svd"]

KEY = jax.random.PRNGKey(11)


def _trees_close(a, b, atol):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        assert jnp.allclose(x, y, atol=atol), float(jnp.max(jnp.abs(x - y)))


# ------------------------------------------------ unit: map_sync_leaves -----

def _mixed_leaves(c=5, r=4):
    """A model-tree-like leaf list: two shape buckets with >1 member (the
    vmapped path), singleton buckets (the skip-vmap path), a left-side leaf,
    a stacked (C, nb, m, r) scan-block leaf pair, and a None (non-adapted)
    leaf. Per-client bases so the same list serves the hetero transfer-Gram
    path."""
    def v_right(key, m):
        return jnp.abs(jax.random.normal(key, (c, m, r))) + 0.1

    def v_left(key, n):
        return jnp.abs(jax.random.normal(key, (c, r, n))) + 0.1

    def b_stack(seed, dim):
        return jnp.stack([proj.random_basis(seed + i, dim, r)
                          for i in range(c)])

    k = [jax.random.fold_in(KEY, i) for i in range(8)]
    v_leaves = [v_right(k[0], 16), v_right(k[1], 16),       # bucket of 2
                v_right(k[2], 12),                          # singleton
                v_left(k[3], 24), v_left(k[4], 24),         # bucket of 2
                None,                                       # non-adapted
                jnp.abs(jax.random.normal(k[5], (c, 3, 16, r))) + 0.1,
                jnp.abs(jax.random.normal(k[6], (c, 3, 16, r))) + 0.1]
    b_leaves = [b_stack(0, 24), b_stack(10, 24),
                b_stack(20, 20),
                b_stack(30, 8), b_stack(40, 8),
                None,
                jnp.stack([b_stack(50 + j, 24) for j in range(3)], axis=1),
                jnp.stack([b_stack(80 + j, 24) for j in range(3)], axis=1)]
    return v_leaves, b_leaves


@pytest.mark.parametrize("protocol", PROTOCOLS)
@pytest.mark.parametrize("hetero", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_map_sync_leaves_bucketed_matches_per_leaf(protocol, hetero, masked):
    """Bucketed vmapped 𝒮 ≡ per-leaf loop across mixed shape buckets, both
    sides, stacked leaves, None passthrough, shared and hetero bases, and
    the masked-cohort (`exclude_zero_weights`) contract."""
    c = 5
    v_leaves, b_leaves = _mixed_leaves(c)
    w = jnp.array([1.0, 2.0, 0.0, 1.0, 3.0]) if masked \
        else jnp.array([1.0, 2.0, 1.0, 1.0, 3.0])

    def leaf_fn(v_stack, bst):
        rank = bst.shape[-1]
        side = proj.RIGHT if v_stack.shape[-1] == rank else proj.LEFT
        if hetero:
            return sync.sync_block_hetero_factored(
                protocol, v_stack, bst, side, w, rank,
                exclude_zero_weights=masked)
        return sync.sync_block_synced_factored(
            protocol, v_stack, side, w, rank, exclude_zero_weights=masked)

    ref = sync.map_sync_leaves_per_leaf(leaf_fn, v_leaves, b_leaves)
    out = sync.map_sync_leaves(leaf_fn, v_leaves, b_leaves)
    assert out[5] is None and ref[5] is None
    for o, rf in zip(out, ref):
        if rf is None:
            assert o is None
            continue
        assert o.shape == rf.shape
        assert jnp.allclose(o, rf, atol=1e-6), float(jnp.max(jnp.abs(o - rf)))


def test_map_sync_leaves_rejects_nothing_on_all_none():
    out = sync.map_sync_leaves(lambda v, b: v, [None, None], [None, None])
    assert out == [None, None]


def test_ajive_sketch_route_matches_dense_oracle():
    """Large-cohort wide-block AJIVE (d > 64 and C·k > 64 → the sketched
    Rayleigh–Ritz joint basis) must still match the dense lift → 𝒮 →
    re-project oracle on a well-separated shared-signal stack, and the
    bucketed dispatch must be exact parity with the per-leaf call."""
    c, m, n, r = 20, 96, 24, 4
    basis = proj.random_basis(0, n, r)
    scale = jnp.linspace(6.0, 2.0, r)
    base = jax.random.normal(KEY, (m, r)) * scale[None, :]
    v_stack = jnp.stack([jnp.abs(base + 0.1 * jax.random.normal(
        jax.random.fold_in(KEY, i), (m, r))) for i in range(c)])
    w = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 99), (c,))) + 0.5

    fact = sync.sync_block_synced_factored("ajive", v_stack, proj.RIGHT, w, r)
    views = jnp.einsum("kmr,nr->kmn", v_stack, basis)
    dense = jnp.maximum(sync.project_state(
        sync.sync_lifted_views("ajive", views, w, r), basis, proj.RIGHT), 0.0)
    assert jnp.allclose(fact, dense, atol=1e-3), \
        float(jnp.max(jnp.abs(fact - dense)))

    out = sync.map_sync_leaves(
        lambda v, b: sync.sync_block_synced_factored(
            "ajive", v, proj.RIGHT, w, r),
        [v_stack, v_stack + 0.01], [jnp.zeros((c, n, r))] * 2)
    assert jnp.allclose(out[0], fact, atol=1e-6)


# ----------------------------------------------------- engine (core.fed) ----

def _problem():
    """Two same-shape hidden layers so the engine's 𝒮 tree has a real
    multi-leaf shape bucket (plus the differently-shaped head)."""
    k1, k2, k3 = (jax.random.fold_in(KEY, i) for i in range(3))
    params = {"l1": {"w": 0.3 * jax.random.normal(k1, (8, 16)),
                     "b": jnp.zeros(16)},
              "l2": {"w": 0.3 * jax.random.normal(k2, (8, 16)),
                     "b": jnp.zeros(16)},
              "head": {"w": 0.3 * jax.random.normal(k3, (16, 4)),
                       "b": jnp.zeros(4)}}

    def loss(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["l1"]["w"] + p["l1"]["b"]
                     + x @ p["l2"]["w"] + p["l2"]["b"])
        out = h @ p["head"]["w"] + p["head"]["b"]
        return jnp.mean((out - y) ** 2)

    return params, loss


def _round_batches(seed, k_rounds=None, k=4, t=5, b=16):
    kb = jax.random.PRNGKey(seed)
    lead = (k, t) if k_rounds is None else (k_rounds, k, t)
    x = jax.random.normal(kb, lead + (b, 8))
    w_true = 0.5 * jax.random.normal(jax.random.fold_in(kb, 1), (8, 4))
    return (x, jnp.einsum("...bi,io->...bo", x, w_true))


def _engine(method, **over):
    params, loss = _problem()
    cfg = dict(method=method, rank=4, lr=3e-2, local_steps=5, clip_norm=10.0,
               weight_decay=0.01)
    cfg.update(over)
    return FedEngine(FedConfig(**cfg), loss, params)


def _per_leaf(fn, *args):
    """``fn(*args)`` jitted with the per-leaf 𝒮 mapping in place of the
    bucketed one. A fresh wrapper is traced inside the swap (jit's trace
    cache is keyed on the function, so ``jax.jit(fn)`` could reuse the
    bucketed trace), and the swap must have been reached unless the method
    has no 𝒮."""
    calls = []

    def per_leaf(*a):
        calls.append(1)
        return sync.map_sync_leaves_per_leaf(*a)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sync, "map_sync_leaves", per_leaf)
        out = jax.jit(lambda *a: fn(*a))(*args)
    assert calls or out is None
    return out


@pytest.mark.parametrize("method", GALORE_METHODS)
def test_engine_bucketed_sync_matches_per_leaf(method):
    """The engine's bucketed 𝒮 ≡ the per-leaf loop on its own uplinked
    states (each round's end-of-round client optimizer states) — covers the
    adaptive round-0 hetero (transfer-Gram) 𝒮 and the shared-basis steady
    state, on a tree with a genuine multi-leaf shape bucket. The bucketed
    result is also what the fused round installed."""
    eng = _engine(method)
    for r in range(2):
        eng.run_round(_round_batches(r))
        w = eng._normalize_weights(None, 4)
        up = eng._client_opt

        def sync_fn(o, r=r):
            return eng._sync_states_pure(o, w, r)
        bucketed = jax.jit(sync_fn)(up)
        per_leaf = _per_leaf(sync_fn, up)
        if bucketed is None:
            assert per_leaf is None and eng.synced_v is None
            continue
        _trees_close(bucketed, per_leaf, atol=1e-6)
        _trees_close(bucketed, eng.synced_v, atol=1e-6)


def _engine_rounds_one_by_one(eng, round_batches, masks=None):
    """K successive run_round calls; (global, synced ṽ, (K, C, T) losses)."""
    k_rounds = jax.tree_util.tree_leaves(round_batches)[0].shape[0]
    losses = [eng.run_round(
        jax.tree_util.tree_map(lambda x, r=r: x[r], round_batches),
        mask=None if masks is None else masks[r])["local_loss"]
        for r in range(k_rounds)]
    return eng.global_trainable, eng.synced_v, jnp.stack(losses)


@pytest.mark.parametrize("method", GALORE_METHODS)
def test_engine_pipelined_rounds_match_sequential(method):
    """Pipelined K-round scan ≡ K successive run_round calls over K=5
    rounds: global trainable, synced moments, and every per-round loss, for
    every GaLore method."""
    rb = _round_batches(3, k_rounds=5)
    eng = _engine(method)
    m = eng.run_rounds(rb)
    pipe = (eng.global_trainable, eng.synced_v, m["local_loss"])
    seq = _engine_rounds_one_by_one(_engine(method), rb)
    for a, b in zip(pipe, seq):
        _trees_close(a, b, atol=1e-6)


def test_engine_pipelined_masked_rounds_match_sequential():
    """Per-round participation masks ride the pipelined scan: the deferred 𝒮
    must use the *previous* round's mask-zeroed weights (carried alongside
    the unsynced states), matching K masked run_round calls exactly."""
    k_rounds, c = 5, 4
    masks = np.ones((k_rounds, c), bool)
    masks[1, 0] = False
    masks[3, 2] = masks[3, 3] = False
    rb = _round_batches(5, k_rounds=k_rounds)
    eng = _engine("fedgalore")
    m = eng.run_rounds(rb, masks=masks)
    pipe = (eng.global_trainable, eng.synced_v, m["local_loss"])
    seq = _engine_rounds_one_by_one(_engine("fedgalore"), rb, masks)
    for a, b in zip(pipe, seq):
        _trees_close(a, b, atol=1e-6)


@pytest.mark.parametrize("robust", ["norm_clip", "trimmed_mean", "geomedian"])
def test_engine_pipelined_robust_agg_matches_sequential(robust):
    """The guarded (robust-𝒜) scan pipelines too: skip_sync captures the
    post-guard effective weights, so deferring 𝒮 by one round changes
    nothing against K guarded run_round calls."""
    rb = _round_batches(7, k_rounds=3)
    eng = _engine("fedgalore", robust_agg=robust)
    m = eng.run_rounds(rb)
    pipe = (eng.global_trainable, eng.synced_v, m["local_loss"])
    seq = _engine_rounds_one_by_one(_engine("fedgalore", robust_agg=robust),
                                    rb)
    for a, b in zip(pipe, seq):
        _trees_close(a, b, atol=1e-6)


# ---------------------------------------------- sharded runtime (fedsim) ----

def _runtime_setup(c_clients=3):
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import TrainSpec

    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    mesh = make_host_mesh(1)
    spec = TrainSpec(rank=4, lr=1e-3, local_steps=2, refresh_mode="random")

    def batches(seed, k_rounds=None):
        kk = jax.random.PRNGKey(seed)
        lead = ((c_clients, 2, 2, 8) if k_rounds is None
                else (k_rounds, c_clients, 2, 2, 8))
        toks = jax.random.randint(kk, lead, 0, cfg.vocab_size)
        return {"tokens": toks, "labels": toks}

    return cfg, mesh, spec, batches


def _runtime_uplink(cfg, spec, mesh, c, bat):
    """The runtime's own uplinked client states after one round: a
    ``state_sync='none'`` federation's round leaves them in place (its 𝒮
    only bumps the seed)."""
    from repro.fedsim import ShardedFederation

    fed = ShardedFederation(cfg, spec, mesh, c, state_sync="none")
    fed.run_round(bat)
    return fed.opt_states


def _runtime_sync_matches_per_leaf(cfg, spec, mesh, c, bat, bases_shared):
    from repro.launch import steps as steps_lib

    up = _runtime_uplink(cfg, spec, mesh, c, bat)
    w = jnp.full((c,), 1.0 / c)

    def sync_fn(st):
        return steps_lib.sync_client_states(st, w, c, "ajive",
                                            bases_shared=bases_shared)
    with jax.set_mesh(mesh):
        bucketed = jax.jit(sync_fn)(up)
        per_leaf = _per_leaf(sync_fn, up)
    _trees_close(bucketed, per_leaf, atol=1e-6)


def test_runtime_bucketed_sync_matches_per_leaf():
    """ShardedFederation's bucketed in-mesh 𝒮 (`steps.sync_client_states`)
    ≡ the per-leaf loop on the runtime's own uplink of the real transformer
    tree (shared seeded bases)."""
    c = 3
    cfg, mesh, spec, batches = _runtime_setup(c)
    _runtime_sync_matches_per_leaf(cfg, spec, mesh, c, batches(0),
                                   bases_shared=True)


def test_runtime_bucketed_hetero_sync_matches_per_leaf():
    """refresh_mode='svd' diverges the bases, so the bucketed 𝒮 runs the
    transfer-Gram hetero path under vmap — must match the per-leaf loop."""
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import TrainSpec

    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    mesh = make_host_mesh(1)
    spec = TrainSpec(rank=4, lr=1e-3, local_steps=2, refresh_mode="svd",
                     refresh_every=2)
    toks = jax.random.randint(jax.random.PRNGKey(3), (3, 2, 2, 8), 0,
                              cfg.vocab_size)
    bat = {"tokens": toks, "labels": toks}
    _runtime_sync_matches_per_leaf(cfg, spec, mesh, 3, bat,
                                   bases_shared=False)


def _runtime_rounds_one_by_one(fed, bat, masks=None):
    """K successive run_round calls; (global, states, (K, C, T) losses)."""
    k_rounds = jax.tree_util.tree_leaves(bat)[0].shape[0]
    losses = [fed.run_round(
        jax.tree_util.tree_map(lambda x, r=r: x[r], bat),
        mask=None if masks is None else masks[r])["losses"]
        for r in range(k_rounds)]
    return fed.global_trainable, fed.opt_states, jnp.stack(losses)


def _restart(fed, start):
    """Put ``fed`` back at round 0 on copies of the ``start`` (global
    trainable, client states): every round donates its inputs. One
    federation per driver then serves every pass, compiling each of its
    programs once."""
    fed.global_trainable, fed.opt_states = jax.tree_util.tree_map(
        jnp.copy, start)
    fed.round_idx = 0
    return fed


def _pipelined_vs_one_by_one(make, bat, mask_sets):
    """For each entry of ``mask_sets``: ``run_rounds`` (pipelined) against
    K successive ``run_round`` calls from the same start, ≤1e-6."""
    pipe_fed, seq_fed = make(), make()
    assert pipe_fed._pipeline_rounds()
    start = jax.tree_util.tree_map(
        jnp.copy, (pipe_fed.global_trainable, pipe_fed.opt_states))
    for mk in mask_sets:
        fed = _restart(pipe_fed, start)
        m = fed.run_rounds(bat, masks=mk)
        pipe = (fed.global_trainable, fed.opt_states, m["losses"])
        seq = _runtime_rounds_one_by_one(_restart(seq_fed, start), bat, mk)
        for a, b in zip(pipe, seq):
            _trees_close(a, b, atol=1e-6)


def test_runtime_pipelined_rounds_match_sequential():
    """Pipelined run_rounds ≡ K successive run_round calls in the sharded
    runtime, unmasked and with per-round participation masks (the deferred
    𝒮 carries each round's mask-zeroed weights)."""
    from repro.fedsim import ShardedFederation

    c, k_rounds = 3, 5
    cfg, mesh, spec, batches = _runtime_setup(c)
    masks = np.ones((k_rounds, c), bool)
    masks[0, 1] = False
    masks[2, 0] = False
    masks[4, 1] = masks[4, 2] = False
    _pipelined_vs_one_by_one(
        lambda: ShardedFederation(cfg, spec, mesh, c, state_sync="ajive"),
        batches(7, k_rounds=k_rounds), (None, masks))


def test_runtime_quarantine_pipelines_and_matches_sequential():
    """Quarantine used to force the sequential scan (the screen rewrites
    effective weights inside the round, invisible to the deferred 𝒮). The
    raw round core returns its post-screen weights (return_weights) and
    they ride the scan carry — so the quarantined scan pipelines AND
    matches K quarantined run_round calls, unmasked and masked. A method
    without 𝒮 has nothing to pipeline."""
    from repro.fedsim import ShardedFederation

    c, k_rounds = 3, 4
    cfg, mesh, spec, batches = _runtime_setup(c)
    masks = np.ones((k_rounds, c), bool)
    masks[1, 0] = False
    masks[3, 2] = False
    _pipelined_vs_one_by_one(
        lambda: ShardedFederation(cfg, spec, mesh, c, state_sync="ajive",
                                  quarantine=True, quarantine_zmax=50.0),
        batches(9, k_rounds=k_rounds), (None, masks))
    no_sync = ShardedFederation(cfg, spec, mesh, c, state_sync="none")
    assert not no_sync._pipeline_rounds()

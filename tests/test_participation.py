"""Planet-scale participation layer: seeded cohort/fault plans, the masked
fused round (full-participation bit-identity, dropout ≡ restricted-cohort
reweighting), bounded stale aggregation (k=0 ≡ synchronous), and the
spill-to-disk client-state store surviving a truncated mid-spill crash."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_fed_round_fused import _problem, _round_batches, _runtime_setup

from repro.core import population as pop
from repro.core.fed import FedConfig, FedEngine


def _engine(method="fedgalore", **over):
    params, loss = _problem()
    kw = dict(method=method, rank=4, lr=3e-2, local_steps=5,
              clip_norm=10.0, weight_decay=0.01)
    kw.update(over)
    return FedEngine(FedConfig(**kw), loss, params)


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(la, lb), float(jnp.max(jnp.abs(la - lb)))


# ------------------------------------------------------------- fault plans --

def test_cohort_plan_deterministic_in_config_and_round():
    pcfg = pop.ParticipationConfig(population=64, dropout_rate=0.3,
                                   straggler_rate=0.4, max_staleness=3,
                                   seed=7)
    for r in range(5):
        a = pop.sample_cohort(pcfg, 8, r)
        b = pop.sample_cohort(pcfg, 8, r)     # call order must not matter
        assert np.array_equal(a.clients, b.clients)
        assert np.array_equal(a.delays, b.delays)
        assert a.clients.shape == (8,)
        assert len(np.unique(a.clients)) == 8          # without replacement
        assert a.clients.max() < 64
        assert a.delays.min() >= -1 and a.delays.max() <= 3
        assert np.array_equal(a.mask, a.delays == 0)
        assert a.mask.any()                   # >= 1 on-time participant


def test_cohort_plan_draw_order_invariance():
    """Disabling staleness must not perturb the upstream sample/dropout
    draws: the (straggler_rate=x, max_staleness=0) plan equals the
    (straggler_rate=0, max_staleness=k) plan exactly."""
    base = dict(population=32, dropout_rate=0.25, seed=3)
    for r in range(6):
        a = pop.sample_cohort(pop.ParticipationConfig(
            straggler_rate=0.6, max_staleness=0, **base), 8, r)
        b = pop.sample_cohort(pop.ParticipationConfig(
            straggler_rate=0.0, max_staleness=4, **base), 8, r)
        assert np.array_equal(a.clients, b.clients)
        assert np.array_equal(a.delays, b.delays)
        assert not (a.delays > 0).any()


def test_cohort_plan_rejects_population_smaller_than_cohort():
    with pytest.raises(ValueError, match="population"):
        pop.sample_cohort(pop.ParticipationConfig(population=3), 4, 0)


# ----------------------------------------------------- masked fused round ---

def test_full_participation_mask_bit_identical_engine():
    """An all-true mask must short-circuit onto the UNMASKED compiled
    program — bit-identity by construction, not numerics."""
    eng_m, eng_p = _engine(), _engine()
    for r in range(2):
        b = _round_batches(r)
        mm = eng_m.run_round(b, mask=np.ones(4, bool))
        mp = eng_p.run_round(b)
        assert np.array_equal(np.asarray(mm["local_loss"]),
                              np.asarray(mp["local_loss"]))
    _leaves_equal(eng_m.global_trainable, eng_p.global_trainable)
    _leaves_equal(eng_m.synced_v, eng_p.synced_v)


def test_mask_dropping_every_client_raises():
    eng = _engine()
    with pytest.raises(ValueError, match="participant"):
        eng.run_round(_round_batches(0), mask=np.zeros(4, bool))


def test_dropout_renormalization_matches_restricted_cohort():
    """A masked C=4 round (one client dropped: zero effective weight in 𝒜,
    excluded from the AJIVE joint basis) must match the C=3 round over just
    the survivors — the eager-reweighting semantics of dropout."""
    mask = np.array([True, True, True, False])
    eng4, eng3 = _engine(), _engine()
    for r in range(2):
        b4 = _round_batches(r)
        b3 = jax.tree_util.tree_map(lambda x: x[:3], b4)
        m4 = eng4.run_round(b4, mask=mask)
        m3 = eng3.run_round(b3)
        assert np.allclose(np.asarray(m4["local_loss"])[:3],
                           np.asarray(m3["local_loss"]), atol=1e-5)
    for la, lb in zip(jax.tree_util.tree_leaves(eng4.global_trainable),
                      jax.tree_util.tree_leaves(eng3.global_trainable)):
        assert jnp.allclose(la, lb, atol=1e-5), float(jnp.max(jnp.abs(la - lb)))
    for la, lb in zip(jax.tree_util.tree_leaves(eng4.synced_v),
                      jax.tree_util.tree_leaves(eng3.synced_v)):
        assert jnp.allclose(la, lb, atol=1e-5), float(jnp.max(jnp.abs(la - lb)))


def test_masked_scan_matches_sequential_masked_rounds():
    """run_rounds(masks=) — per-round effective weights riding the scan as
    xs — must reproduce K sequential run_round(mask=) calls."""
    masks = np.array([[True, True, True, True],
                      [True, False, True, True],
                      [True, True, False, False]])
    eng_s, eng_q = _engine(), _engine()
    rb = _round_batches(0, k_rounds=3)
    ms = eng_s.run_rounds(rb, masks=masks)
    for r in range(3):
        mq = eng_q.run_round(jax.tree_util.tree_map(lambda x: x[r], rb),
                             mask=masks[r])
        assert np.allclose(np.asarray(ms["local_loss"][r]),
                           np.asarray(mq["local_loss"]), atol=1e-5)
    for la, lb in zip(jax.tree_util.tree_leaves(eng_s.global_trainable),
                      jax.tree_util.tree_leaves(eng_q.global_trainable)):
        assert jnp.allclose(la, lb, atol=1e-5), float(jnp.max(jnp.abs(la - lb)))


# -------------------------------------------------------- population runner --

def _runner(eng, pcfg, **kw):
    return pop.PopulationRunner(
        eng, lambda ids, r: _round_batches(r), cohort=4, pcfg=pcfg, **kw)


def test_staleness_zero_is_exactly_synchronous():
    """max_staleness=0 disables buffering entirely (delay-0 ≡ on-time), so
    the PopulationRunner with no dropout is bit-identical to bare engine
    rounds: the full-participation plan short-circuits to the unmasked
    program."""
    eng_r = _engine()
    runner = _runner(eng_r, pop.ParticipationConfig(
        straggler_rate=0.9, max_staleness=0, seed=5))
    eng_p = _engine()
    for r in range(3):
        rec = runner.run_round()
        assert rec["participants"] == 4
        assert rec["buffered"] == 0 and rec["stale_merged"] == 0
        mp = eng_p.run_round(_round_batches(r))
        assert np.array_equal(np.asarray(rec["local_loss"]),
                              np.asarray(mp["local_loss"]))
    _leaves_equal(eng_r.global_trainable, eng_p.global_trainable)
    _leaves_equal(eng_r.synced_v, eng_p.synced_v)


def test_population_runner_faulted_rounds(tmp_path):
    """End-to-end fault injection: dropped clients, buffered stragglers
    landing at their due round, drift observatory recording, sticky rows
    scattered for the live clients only."""
    pcfg = pop.ParticipationConfig(population=16, dropout_rate=0.25,
                                   straggler_rate=0.5, max_staleness=2,
                                   seed=11)
    eng = _engine()
    runner = _runner(eng, pcfg, store_dir=str(tmp_path), shard_size=4,
                     max_resident_shards=2)
    out = runner.run_rounds(6)
    hist = out["history"]
    assert len(hist) == 6
    planned_stragglers = sum(
        int((pop.sample_cohort(pcfg, 4, r, 16).delays > 0).sum())
        for r in range(6))
    assert planned_stragglers > 0          # seed 11 does produce stragglers
    merged = sum(h["stale_merged"] for h in hist)
    assert merged > 0                      # ... and they land
    assert merged + len(runner.buffer) == planned_stragglers
    for h in hist:
        assert h["participants"] >= 1
        assert np.isfinite(h["mean_final_loss"])
        assert h["moment_divergence"] >= 0.0
        if h["stale_merged"]:
            assert 0.0 < h["stale_weight_err"] < 1.0
    for leaf in jax.tree_util.tree_leaves(eng.global_trainable):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # Live clients got sticky rows; flush spilled the dirty shards.
    assert (runner.store.last_round >= 0).any()
    assert runner.store.spills > 0


# ------------------------------------------------------ client-state store --

def _store_template():
    return {"delta": np.zeros((3, 2), np.float32),
            "v": np.zeros((5,), np.float32)}


def test_store_gather_scatter_roundtrip_with_spill(tmp_path):
    """10⁴ clients through a 4-shard resident window: every scattered row
    reads back exactly, cold clients read zeros, and a second store on the
    same directory sees the flushed rows (persistence)."""
    n = 10_000
    rng = np.random.default_rng(0)
    store = pop.ClientStateStore(n, _store_template(), str(tmp_path),
                                 shard_size=256, max_resident_shards=4)
    ids = np.sort(rng.choice(n, size=200, replace=False))
    rows = {"delta": rng.normal(size=(200, 3, 2)).astype(np.float32),
            "v": rng.normal(size=(200, 5)).astype(np.float32)}
    store.scatter(ids, rows, round_idx=3)
    assert store.spills > 0                # the LRU window forced spills
    got = store.gather(ids)
    np.testing.assert_array_equal(got["delta"], rows["delta"])
    np.testing.assert_array_equal(got["v"], rows["v"])
    cold = store.gather(np.setdiff1d(np.arange(300), ids)[:50])
    assert not cold["delta"].any() and not cold["v"].any()
    assert (store.last_round[ids] == 3).all()

    store.flush()
    reopened = pop.ClientStateStore(n, _store_template(), str(tmp_path),
                                    shard_size=256, max_resident_shards=4)
    got2 = reopened.gather(ids)
    np.testing.assert_array_equal(got2["delta"], rows["delta"])


def test_store_truncated_spill_falls_back_cold(tmp_path):
    """A spill cut short mid-write (simulated by truncating the shard's npz
    payload) must read back as cold zeros — not crash the run — while
    intact shards are untouched."""
    store = pop.ClientStateStore(64, _store_template(), str(tmp_path),
                                 shard_size=16, max_resident_shards=8)
    ids = np.arange(64)
    rows = {"delta": np.ones((64, 3, 2), np.float32),
            "v": np.ones((64, 5), np.float32)}
    store.scatter(ids, rows)
    store.flush()
    victim = os.path.join(str(tmp_path), "clients_00000001.npz")
    sz = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(sz // 2)

    reopened = pop.ClientStateStore(64, _store_template(), str(tmp_path),
                                    shard_size=16, max_resident_shards=8)
    got = reopened.gather(ids)
    assert not got["delta"][16:32].any()       # crashed shard: cold zeros
    assert got["delta"][:16].all()             # neighbors intact
    assert got["delta"][32:].all()


def test_store_spill_requires_directory():
    with pytest.raises(ValueError, match="spill"):
        pop.ClientStateStore(64, _store_template(), directory=None,
                             shard_size=16, max_resident_shards=2)


# ------------------------------------------------------- staleness buffer ---

def test_staleness_buffer_pops_by_due_round():
    buf = pop.StalenessBuffer()
    mk = lambda cid, due: pop.StaleEntry(
        client_id=cid, birth_round=0, due_round=due, weight=0.25, decay=0.5,
        base_scale=1.0, deltas={"w": np.ones(2)}, bases=None, v_rows=None)
    buf.push(mk(1, 2))
    buf.push(mk(2, 1))
    buf.push(mk(3, 3))
    assert len(buf) == 3 and buf.pending_rounds == [1, 2, 3]
    due = buf.pop_due(2)
    assert sorted(e.client_id for e in due) == [1, 2]
    assert len(buf) == 1 and buf.pending_rounds == [3]


# ------------------------------------------------------- drift observatory --

def test_moment_divergence_zero_when_rows_match_bar():
    bar = {"w": np.full((3, 4), 2.0), "skip": None}
    rows = {"w": np.broadcast_to(bar["w"], (5, 3, 4)).copy(), "skip": None}
    assert pop.moment_divergence(rows, bar) == pytest.approx(0.0, abs=1e-9)
    rows2 = {"w": rows["w"] + 1.0, "skip": None}
    d = pop.moment_divergence(rows2, bar)
    # all rows offset by 1: dispersion sqrt(12)/||v̄|| = sqrt(12)/sqrt(48)
    assert d == pytest.approx(0.5, rel=1e-6)


def test_tree_rel_err():
    a = {"x": np.ones(4), "none": None}
    b = {"x": np.ones(4), "none": None}
    assert pop.tree_rel_err(a, b) == pytest.approx(0.0, abs=1e-12)
    a2 = {"x": np.ones(4) * 1.1, "none": None}
    assert pop.tree_rel_err(a2, b) == pytest.approx(0.1, rel=1e-6)


# ------------------------------------------------------------ runtime path --

def test_sharded_runtime_participation_layer():
    """ShardedFederation: all-true mask bit-identical to the unmasked round;
    sample_round_mask honors the ParticipationConfig; the masked scan driver
    matches sequential masked rounds."""
    from repro.fedsim import ShardedFederation

    c = 3
    cfg, mesh, spec, batches = _runtime_setup(c)
    pcfg = pop.ParticipationConfig(dropout_rate=0.5, seed=9)

    fed_m = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive",
                              participation=pcfg)
    fed_p = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive")
    b = batches(0)
    mm = fed_m.run_round(b, mask=np.ones(c, bool))    # short-circuit
    mp = fed_p.run_round(b)
    assert np.array_equal(np.asarray(mm["losses"]), np.asarray(mp["losses"]))
    for la, lb in zip(jax.tree_util.tree_leaves(fed_m.global_trainable),
                      jax.tree_util.tree_leaves(fed_p.global_trainable)):
        assert jnp.array_equal(la, lb)

    masks = np.stack([fed_m.sample_round_mask(r) for r in (1, 2)])
    assert masks.shape == (2, c)
    assert masks.any(axis=1).all()            # every round has a participant
    # seeded + pure in (config, round): re-sampling gives the same masks
    assert np.array_equal(masks[0], fed_m.sample_round_mask(1))

    if not masks.all():                       # exercise the masked program
        fed_s = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive",
                                  participation=pcfg)
        ms = fed_s.run_rounds(batches(7, k_rounds=2), masks=masks)
        fed_q = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive",
                                  participation=pcfg)
        for r in range(2):
            mq = fed_q.run_round(jax.tree_util.tree_map(
                lambda x: x[r], batches(7, k_rounds=2)), mask=masks[r])
            assert np.allclose(np.asarray(ms["losses"][r]),
                               np.asarray(mq["losses"]), atol=1e-5)
        for la, lb in zip(jax.tree_util.tree_leaves(fed_s.global_trainable),
                          jax.tree_util.tree_leaves(fed_q.global_trainable)):
            assert jnp.allclose(la, lb, atol=1e-5)

"""Defense-in-depth rounds: in-round quarantine of corrupted factored
contributions (all-honest bit-identity, NaN/scale attacks ≈ masked-round
parity), robust factored aggregation operators (norm-clip / trimmed-mean /
geomedian on rank-r stacks), seeded corruption plans, bounded staleness
buffers, crash-resumable snapshots, and the drift tripwire's
rollback-and-replay path."""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_fed_round_fused import _problem, _round_batches, _runtime_setup

from repro.core import aggregation as agg
from repro.core import population as pop
from repro.core.fed import FedConfig, FedEngine


def _engine(**over):
    params, loss = _problem()
    kw = dict(method="fedgalore", rank=4, lr=3e-2, local_steps=5,
              clip_norm=10.0, weight_decay=0.01)
    kw.update(over)
    return FedEngine(FedConfig(**kw), loss, params)


def _runner(eng, pcfg=None, **kw):
    return pop.PopulationRunner(eng, lambda ids, r: _round_batches(r),
                                cohort=4, pcfg=pcfg, **kw)


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.array_equal(la, lb), float(jnp.max(jnp.abs(la - lb)))


def _finite_tree(t):
    for leaf in jax.tree_util.tree_leaves(t):
        assert np.isfinite(np.asarray(leaf)).all()


# ------------------------------------------------- robust operator units ----

def test_client_sq_norms_ignores_nonfinite():
    stack = jnp.asarray([[1.0, 2.0], [np.nan, 3.0], [np.inf, 1.0]])
    n = np.asarray(agg.client_sq_norms(stack))
    np.testing.assert_allclose(n, [5.0, 9.0, 1.0])


def test_weighted_quantile_median():
    x = jnp.asarray([1.0, 5.0, 3.0])
    w = jnp.asarray([1.0, 1.0, 1.0]) / 3
    assert float(agg.weighted_quantile(x, w, 0.5)) == 3.0
    # Skewed mass pulls the median onto the heavy sample.
    w2 = jnp.asarray([0.8, 0.1, 0.1])
    assert float(agg.weighted_quantile(x, w2, 0.5)) == 1.0


def test_median_norm_clip_caps_outlier_only():
    stack = jnp.stack([jnp.ones((3, 2)), jnp.ones((3, 2)),
                       100.0 * jnp.ones((3, 2))])
    w = jnp.full((3,), 1 / 3)
    c = np.asarray(agg.median_norm_clip_factors(stack, w))
    np.testing.assert_allclose(c[:2], 1.0)
    assert c[2] == pytest.approx(1.0 / 100.0, rel=1e-5)


def test_trimmed_mean_zero_trim_is_weighted_mean():
    rng = np.random.default_rng(0)
    stack = jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)
    w = jnp.asarray(rng.random(5), jnp.float32)
    w = w / w.sum()
    got = agg.robust_factored_reduce(stack, w, "trimmed_mean", trim=0.0)
    ref = jnp.einsum("c,c...->...", w, stack)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_trimmed_mean_and_geomedian_resist_outlier():
    honest = jnp.ones((4, 3, 2))
    stack = jnp.concatenate([honest, 1e4 * jnp.ones((1, 3, 2))])
    w = jnp.full((5,), 0.2)
    for mode in ("trimmed_mean", "geomedian"):
        out = np.asarray(agg.robust_factored_reduce(stack, w, mode,
                                                    trim=0.25))
        assert np.abs(out - 1.0).max() < 0.1, (mode, out)
    # The plain mean is dragged three orders of magnitude away.
    mean = np.asarray(jnp.einsum("c,c...->...", w, stack))
    assert mean.min() > 1e3


def test_robust_reduce_excludes_zero_weight_rows():
    stack = jnp.stack([jnp.ones((2, 2)), 3.0 * jnp.ones((2, 2)),
                       1e6 * jnp.ones((2, 2))])
    w = jnp.asarray([0.5, 0.5, 0.0])
    for mode in ("trimmed_mean", "geomedian"):
        out = np.asarray(agg.robust_factored_reduce(stack, w, mode,
                                                    trim=0.0))
        assert out.max() < 10.0, (mode, out)


def test_screen_factored_clients_flags_nonfinite_and_outliers():
    d = {"a": jnp.ones((4, 3, 2))}
    v = {"a": jnp.ones((4, 3, 2))}
    scales = jnp.ones((4,))
    w = jnp.full((4,), 0.25)
    keep = np.asarray(agg.screen_factored_clients(d, v, scales, w))
    assert keep.all()
    bad_d = {"a": d["a"].at[1].set(jnp.nan).at[2].mul(1e4)}
    keep = np.asarray(agg.screen_factored_clients(bad_d, v, scales, w,
                                                  zmax=6.0))
    np.testing.assert_array_equal(keep, [True, False, False, True])


def test_quarantine_weights_allpass_untouched_partial_renormalized():
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    out = agg.quarantine_weights(w, jnp.ones((4,), bool))
    assert jnp.array_equal(out, w)          # bitwise: no renorm round-off
    keep = jnp.asarray([True, False, True, False])
    out = np.asarray(agg.quarantine_weights(w, keep))
    np.testing.assert_allclose(out, [0.25, 0.0, 0.75, 0.0], atol=1e-6)
    # All-fail degrades to the original weights (skip-round semantics).
    out = agg.quarantine_weights(w, jnp.zeros((4,), bool))
    assert jnp.array_equal(out, w)


# ------------------------------------------------ guarded engine rounds -----

def test_guarded_round_honest_bit_identity_engine():
    """quarantine=True with an all-honest cohort must reproduce the
    unguarded engine bit-for-bit — the screen, the weight fold, and the
    moment reinstall are exact float identities, not numerics."""
    eng_q, eng_p = _engine(quarantine=True), _engine()
    for r in range(3):
        b = _round_batches(r)
        mq = eng_q.run_round(b)
        mp = eng_p.run_round(b)
        assert jnp.array_equal(mq["local_loss"], mp["local_loss"])
    _leaves_equal(eng_q.global_trainable, eng_p.global_trainable)
    _leaves_equal(eng_q.synced_v, eng_p.synced_v)


def test_all_ones_attack_canonicalizes_to_unattacked():
    """An explicit all-ones attack operand short-circuits onto the plain
    program (no guarded compile, bit-identical outputs)."""
    eng_a, eng_p = _engine(), _engine()
    for r in range(2):
        b = _round_batches(r)
        ma = eng_a.run_round(b, attack=np.ones(4, np.float32))
        mp = eng_p.run_round(b)
        assert jnp.array_equal(ma["local_loss"], mp["local_loss"])
    _leaves_equal(eng_a.global_trainable, eng_p.global_trainable)
    assert eng_a._round_guard_jit is None   # guarded program never built


@pytest.mark.parametrize("attack_val", [np.nan, 100.0],
                         ids=["nan", "scale"])
def test_quarantine_matches_masked_round(attack_val):
    """A quarantined attacker ≈ the same client masked out: the screen
    zeroes its contribution and renormalizes the survivors. allclose (not
    bitwise) because the masked path renormalizes eagerly on the host."""
    eng_a, eng_m = _engine(quarantine=True), _engine()
    attack = np.ones(4, np.float32)
    attack[1] = attack_val
    mask = np.ones(4, bool)
    mask[1] = False
    for r in range(2):
        b = _round_batches(r)
        eng_a.run_round(b, attack=attack)
        eng_m.run_round(b, mask=mask)
    _finite_tree(eng_a.global_trainable)
    for la, lb in zip(jax.tree_util.tree_leaves(eng_a.global_trainable),
                      jax.tree_util.tree_leaves(eng_m.global_trainable)):
        assert jnp.allclose(la, lb, atol=1e-5), float(
            jnp.max(jnp.abs(la - lb)))


def test_robust_agg_bounds_scale_attack():
    """Under a 100× norm attack on one client, trimmed-mean aggregation
    stays near the honest trajectory while mode 'none' is dragged away."""
    honest = _engine()
    plain = _engine()
    robust = _engine(robust_agg="trimmed_mean", robust_trim=0.3)
    attack = np.ones(4, np.float32)
    attack[2] = 100.0
    for r in range(2):
        b = _round_batches(r)
        honest.run_round(b)
        plain.run_round(b, attack=attack)
        robust.run_round(b, attack=attack)
    err_plain = pop.tree_rel_err(plain.global_trainable,
                                 honest.global_trainable)
    err_robust = pop.tree_rel_err(robust.global_trainable,
                                  honest.global_trainable)
    assert err_robust < 0.1 * err_plain, (err_robust, err_plain)
    _finite_tree(robust.global_trainable)


def test_guarded_round_requires_factored_clients():
    """The guard runs on rank-r factored stacks: a method without factored
    clients (a LoRA baseline) refuses a quarantine config and an attack."""
    with pytest.raises(ValueError, match="factored"):
        _engine(method="fedit", quarantine=True)
    eng = _engine(method="fedit")
    assert not eng._factored
    attack = np.ones(4, np.float32)
    attack[0] = -1.0          # all-ones canonicalizes away; this cannot
    with pytest.raises(ValueError, match="factored"):
        eng.run_round(_round_batches(0), attack=attack)


# ---------------------------------------------------- corruption plans ------

def test_corruption_plan_deterministic_and_on_time_only():
    pcfg = pop.ParticipationConfig(population=32, dropout_rate=0.2,
                                   straggler_rate=0.3, max_staleness=2,
                                   corrupt_rate=0.4, seed=11)
    saw = 0
    for r in range(8):
        a = pop.sample_cohort(pcfg, 8, r)
        b = pop.sample_cohort(pcfg, 8, r)
        assert np.array_equal(a.corrupt, b.corrupt)
        assert not a.corrupt[~a.mask].any()      # only on-time corrupted
        assert (a.mask & (a.corrupt == 0)).any()  # >= 1 honest on-time
        saw += int((a.corrupt != 0).sum())
    assert saw > 0


def test_corruption_draw_order_invariance():
    """Enabling the adversary must not perturb the upstream fault draws."""
    base = dict(population=32, dropout_rate=0.25, straggler_rate=0.3,
                max_staleness=3, seed=4)
    for r in range(6):
        a = pop.sample_cohort(pop.ParticipationConfig(**base), 8, r)
        b = pop.sample_cohort(pop.ParticipationConfig(
            corrupt_rate=0.5, **base), 8, r)
        assert np.array_equal(a.clients, b.clients)
        assert np.array_equal(a.delays, b.delays)


def test_fully_adversarial_config_raises():
    with pytest.raises(ValueError, match="honest"):
        pop.sample_cohort(pop.ParticipationConfig(corrupt_rate=1.0), 4, 0)
    with pytest.raises(ValueError, match="corrupt mode"):
        pop.sample_cohort(pop.ParticipationConfig(
            corrupt_rate=0.5, corrupt_modes=("bitflip",)), 4, 0)


def test_corruption_pardon_keeps_one_honest():
    """At corrupt_rate just under 1, rounds where every on-time client drew
    corrupted still keep one pardoned honest participant."""
    pcfg = pop.ParticipationConfig(corrupt_rate=0.999, seed=0)
    for r in range(6):
        plan = pop.sample_cohort(pcfg, 4, r)
        assert (plan.mask & (plan.corrupt == 0)).any()


def test_corruption_multipliers_mapping():
    pcfg = pop.ParticipationConfig(corrupt_rate=0.5,
                                   corrupt_modes=("nan", "sign_flip",
                                                  "scale"),
                                   attack_scale=50.0)
    plan = pop.CohortPlan(round_idx=0, clients=np.arange(4),
                          mask=np.ones(4, bool),
                          delays=np.zeros(4, np.int64),
                          corrupt=np.asarray([0, 1, 2, 3]))
    m = pop.corruption_multipliers(plan, pcfg)
    assert m[0] == 1.0 and np.isnan(m[1]) and m[2] == -1.0 and m[3] == 50.0
    honest = plan._replace(corrupt=np.zeros(4, np.int64))
    assert pop.corruption_multipliers(honest, pcfg) is None
    assert pop.corruption_multipliers(plan._replace(corrupt=None),
                                      pcfg) is None


def test_corrupted_rounds_stay_finite_end_to_end():
    """NaN adversaries on up to half the cohort: the quarantined runner's
    loss/drift records and global state stay finite, and corrupted clients
    never scatter poisoned rows into the store."""
    pcfg = pop.ParticipationConfig(corrupt_rate=0.5, corrupt_modes=("nan",),
                                   seed=5)
    run = _runner(_engine(quarantine=True), pcfg)
    out = run.run_rounds(4)
    assert sum(r["corrupted"] for r in out["history"]) > 0
    for rec in out["history"]:
        assert np.isfinite(rec["mean_final_loss"])
        assert np.isfinite(rec["moment_divergence"])
    _finite_tree(run.engine.global_trainable)
    _finite_tree(run.store.gather(np.arange(4)))


# ------------------------------------------------- staleness buffer caps ----

def _entry(cid, due):
    return pop.StaleEntry(client_id=cid, birth_round=0, due_round=due,
                          weight=0.25, decay=0.5, base_scale=1.0,
                          deltas={"a": np.ones(2, np.float32)}, bases=None,
                          v_rows=None)


def test_staleness_buffer_evicts_earliest_due_at_capacity():
    buf = pop.StalenessBuffer(capacity=2)
    assert buf.push(_entry(0, due=5)) is None
    assert buf.push(_entry(1, due=3)) is None
    evicted = buf.push(_entry(2, due=4))
    assert evicted is not None and evicted.client_id == 1   # earliest due
    assert buf.evictions == 1 and len(buf) == 2
    assert sorted(e.client_id for e in buf._entries) == [0, 2]
    # FIFO tie-break on equal due rounds.
    evicted = buf.push(_entry(3, due=4))
    assert evicted.client_id == 2
    with pytest.raises(ValueError, match="capacity"):
        pop.StalenessBuffer(capacity=0)


def test_full_buffer_never_blocks_on_time_clients():
    """With a capacity-1 buffer under a straggler-heavy plan, on-time
    contributions bypass the buffer entirely (delay-0 ≡ synchronous) and
    rounds keep landing; overflow shows up only as recorded evictions."""
    pcfg = pop.ParticipationConfig(straggler_rate=0.6, max_staleness=3,
                                   seed=2)
    run = _runner(_engine(), pcfg, buffer_capacity=1)
    out = run.run_rounds(5)
    assert len(run.buffer) <= 1
    assert sum(r["stale_evicted"] for r in out["history"]) > 0
    assert sum(r["straggling"] for r in out["history"]) > 0
    for rec in out["history"]:
        assert np.isfinite(rec["mean_final_loss"])
    _finite_tree(run.engine.global_trainable)


# ---------------------------------------------- snapshots: kill & resume ----

def test_snapshot_kill_resume_loss_parity(tmp_path):
    """Kill-and-resume: a fresh runner restored from the latest snapshot
    replays the remaining rounds with loss-curve parity against the
    uninterrupted run, and retention keeps only ``snapshot_keep``."""
    snap = str(tmp_path / "snaps")
    pc = pop.ParticipationConfig(dropout_rate=0.2, straggler_rate=0.3,
                                 max_staleness=2, seed=9)
    ra = _runner(_engine(), pc, snapshot_dir=snap, snapshot_every=1,
                 snapshot_keep=2)
    ra.run_rounds(3)

    rb = _runner(_engine(), pc, snapshot_dir=snap)
    step = rb.restore()
    assert step == 3 and rb.engine.round_idx == 3
    assert len(rb.history) == 3

    ra.run_rounds(3)
    rb.run_rounds(3)
    ref = [r["mean_final_loss"] for r in ra.history[3:]]
    got = [r["mean_final_loss"] for r in rb.history[3:]]
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    np.testing.assert_allclose(
        [r["moment_divergence"] for r in rb.history[3:]],
        [r["moment_divergence"] for r in ra.history[3:]], rtol=1e-5,
        atol=1e-8)
    assert len([f for f in os.listdir(snap) if f.endswith(".npz")]) == 2


def test_snapshot_restores_staleness_buffer(tmp_path):
    """In-flight stale entries survive the crash: the restored buffer merges
    the same due updates the uninterrupted run does."""
    snap = str(tmp_path / "snaps")
    pc = pop.ParticipationConfig(straggler_rate=0.6, max_staleness=3, seed=2)
    ra = _runner(_engine(), pc, snapshot_dir=snap, snapshot_every=1)
    ra.run_rounds(2)
    assert len(ra.buffer) > 0                  # something is in flight
    rb = _runner(_engine(), pc, snapshot_dir=snap)
    rb.restore()
    assert len(rb.buffer) == len(ra.buffer)
    ra.run_rounds(3)
    rb.run_rounds(3)
    assert ([r["stale_merged"] for r in ra.history]
            == [r["stale_merged"] for r in rb.history])
    np.testing.assert_allclose(
        [r["mean_final_loss"] for r in rb.history[2:]],
        [r["mean_final_loss"] for r in ra.history[2:]], rtol=1e-6)


def test_restore_without_snapshot_raises(tmp_path):
    run = _runner(_engine(), snapshot_dir=str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        run.restore()
    run2 = _runner(_engine())
    with pytest.raises(ValueError, match="snapshot_dir"):
        run2.snapshot()


# ------------------------------------------------------- drift tripwire -----

def test_tripwire_rolls_back_and_replays_without_offenders():
    """NaN adversaries with in-round quarantine OFF: the drift tripwire
    detects the poisoned round, rolls the federation back, screens the
    harvested uplink host-side, and replays with the offenders quarantined
    — no warning, finite state."""
    pcfg = pop.ParticipationConfig(corrupt_rate=0.5, corrupt_modes=("nan",),
                                   seed=5)
    run = _runner(_engine(), pcfg, drift_tripwire=1e6, tripwire_retries=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        recs = [run.run_round() for _ in range(3)]
    assert any(r["tripwire_replays"] > 0 for r in recs)
    for rec in recs:
        assert np.isfinite(rec["mean_final_loss"])
        assert rec["tripwire_quarantined"] >= rec["tripwire_replays"]
    _finite_tree(run.engine.global_trainable)
    # history mirrors the replayed (clean) rounds, one record per round
    assert len(run.history) == 3


def test_tripwire_degrades_with_warning_when_out_of_retries():
    pcfg = pop.ParticipationConfig(corrupt_rate=0.5, corrupt_modes=("nan",),
                                   seed=5)
    run = _runner(_engine(), pcfg, drift_tripwire=1e6, tripwire_retries=0)
    with pytest.warns(UserWarning, match="tripwire"):
        rec = run.run_round()
    assert rec["tripwire_replays"] == 0


def test_tripwire_noop_on_honest_rounds():
    """An armed tripwire over honest rounds must not replay or warn, and
    the trajectory must match the unarmed runner exactly."""
    pc = pop.ParticipationConfig(dropout_rate=0.2, seed=3)
    ra = _runner(_engine(), pc, drift_tripwire=1e6, loss_tripwire=1e6)
    rb = _runner(_engine(), pc)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for _ in range(3):
            ra.run_round()
            rb.run_round()
    assert all(r["tripwire_replays"] == 0 for r in ra.history)
    _leaves_equal(ra.engine.global_trainable, rb.engine.global_trainable)


# --------------------------------------------------- runtime bit-identity ---

def test_sharded_runtime_quarantine_honest_bit_identity():
    """ShardedFederation with quarantine=True over an honest cohort must
    match the unguarded runtime bit-for-bit (same identities as the
    engine: exact screen no-op + untouched weights). zmax is pinned high
    enough that the *verdict* passes everyone: the 3-client random-token
    smoke cohort legitimately disperses past the default 6× median norm,
    and a passing screen — not the verdict policy — is the exactness
    contract under test (a failing verdict is quarantine doing its job)."""
    from repro.fedsim import ShardedFederation

    c_clients = 3
    cfg, mesh, spec, batches = _runtime_setup(c_clients)
    fed_q = ShardedFederation(cfg, spec, mesh, c_clients,
                              state_sync="ajive", quarantine=True,
                              quarantine_zmax=50.0)
    fed_p = ShardedFederation(cfg, spec, mesh, c_clients,
                              state_sync="ajive")
    for r in range(2):
        b = batches(r)
        mq = fed_q.run_round(b)
        mp = fed_p.run_round(b)
        assert jnp.array_equal(mq["losses"], mp["losses"])
    _leaves_equal(fed_q.global_trainable, fed_p.global_trainable)


def test_sharded_runtime_rejects_robust_dense_clients():
    """Refreshes that do not land on local step 0 keep dense client stacks,
    which the quarantine screen cannot read."""
    import dataclasses

    from repro.fedsim import ShardedFederation

    cfg, mesh, spec, _ = _runtime_setup(3)
    spec = dataclasses.replace(spec, refresh_every=3)
    assert spec.refresh_every % spec.local_steps
    fed = ShardedFederation(cfg, spec, mesh, 3, state_sync="ajive",
                            quarantine=True)
    with pytest.raises(ValueError, match="factored"):
        fed.run_round({"tokens": np.zeros((3, 2, 2, 8), np.int32),
                       "labels": np.zeros((3, 2, 2, 8), np.int32)})


# ---------------------------------------- basis-coherent hetero robustness --

def _orthonormal(m, r, seed):
    q, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(m, r)))
    return np.asarray(q, np.float32)


def test_rebase_shared_basis_is_identity():
    """All clients on one orthonormal basis: the transfer Grams are exact
    identities and re-basing returns the stack unchanged (up to fp32)."""
    rng = np.random.default_rng(1)
    b = _orthonormal(6, 3, 0)
    bases = jnp.asarray(np.broadcast_to(b, (4,) + b.shape))
    right = jnp.asarray(rng.normal(size=(4, 5, 3)), jnp.float32)
    out = agg.rebase_factored_stack(right, bases, "right")
    np.testing.assert_allclose(np.asarray(out), np.asarray(right), atol=1e-5)
    left = jnp.asarray(rng.normal(size=(4, 3, 5)), jnp.float32)
    out = agg.rebase_factored_stack(left, bases, "left")
    np.testing.assert_allclose(np.asarray(out), np.asarray(left), atol=1e-5)


def test_rebase_aligns_rotated_bases():
    """Clients observing the SAME ambient update through rotated bases
    (Bᵢ = B₀Qᵢ spans the same subspace) disagree coordinate-wise; after
    re-basing onto client 0's basis every honest row coincides with R₀ —
    the property that makes coordinate-wise votes basis-coherent."""
    rng = np.random.default_rng(3)
    m, n, r, c = 7, 5, 3, 4
    b0 = _orthonormal(n, r, 0)
    ambient = rng.normal(size=(m, n)).astype(np.float32)
    bases, coords = [], []
    for i in range(c):
        q, _ = np.linalg.qr(rng.normal(size=(r, r)))
        bi = b0 @ q.astype(np.float32)
        bases.append(bi)
        coords.append(ambient @ bi)                      # side 'right'
    stack = jnp.asarray(np.stack(coords))
    out = np.asarray(agg.rebase_factored_stack(
        stack, jnp.asarray(np.stack(bases)), "right"))
    ref = ambient @ bases[0]       # everything lands on client 0's basis
    for i in range(c):
        np.testing.assert_allclose(out[i], ref, atol=1e-4)


def test_robust_hetero_lift_basis_coherent_outlier():
    """Rotated honest bases + one 100x attacker: the coordinate-wise robust
    modes re-base first and recover the honest ambient update, while the
    plain hetero mean is dragged."""
    rng = np.random.default_rng(4)
    m, n, r, c = 7, 5, 3, 5
    b0 = _orthonormal(n, r, 0)
    ambient = rng.normal(size=(m, n)).astype(np.float32)
    honest_lift = (ambient @ b0) @ b0.T                  # P-projected update
    bases, coords = [], []
    for i in range(c):
        q, _ = np.linalg.qr(rng.normal(size=(r, r)))
        bi = b0 @ q.astype(np.float32)
        bases.append(bi)
        coords.append(ambient @ bi * (100.0 if i == c - 1 else 1.0))
    stack = jnp.asarray(np.stack(coords))
    bstack = jnp.asarray(np.stack(bases))
    w = jnp.full((c,), 1.0 / c)
    for mode in ("trimmed_mean", "geomedian"):
        out = np.asarray(agg.robust_factored_lift(
            stack, bstack, "right", w, mode, hetero=True, trim=0.25))
        err = np.abs(out - honest_lift).max()
        assert err < 0.05 * np.abs(honest_lift).max(), (mode, err)
    dragged = np.asarray(agg.robust_factored_lift(
        stack, bstack, "right", w, "none", hetero=True))
    assert np.abs(dragged - honest_lift).max() > np.abs(honest_lift).max()


def test_robust_hetero_lift_matches_shared_on_shared_bases():
    """hetero=True with identical bases must agree with the shared-basis
    robust lift: re-basing through identity Grams is a no-op."""
    rng = np.random.default_rng(5)
    b = _orthonormal(6, 3, 1)
    bases = jnp.asarray(np.broadcast_to(b, (4,) + b.shape))
    stack = jnp.asarray(rng.normal(size=(4, 5, 3)), jnp.float32)
    w = jnp.asarray([0.4, 0.3, 0.2, 0.1])
    for mode in ("trimmed_mean", "geomedian", "norm_clip"):
        het = np.asarray(agg.robust_factored_lift(
            stack, bases, "right", w, mode, hetero=True))
        shared = np.asarray(agg.robust_factored_lift(
            stack, bases, "right", w, mode, hetero=False))
        np.testing.assert_allclose(het, shared, atol=1e-5, err_msg=mode)


# ----------------------------------------------------------- robust 𝒮 ------

def test_robust_sync_bounds_moment_drag():
    """A 100x scale attack poisons the projected-moment stacks feeding 𝒮;
    with robust_agg='trimmed_mean' the synced moments stay near the honest
    trajectory instead of being dragged with the plain weighted mean."""
    honest = _engine()
    plain = _engine()
    robust = _engine(robust_agg="trimmed_mean", robust_trim=0.3)
    attack = np.ones(4, np.float32)
    attack[2] = 100.0
    for r in range(2):
        b = _round_batches(r)
        honest.run_round(b)
        plain.run_round(b, attack=attack)
        robust.run_round(b, attack=attack)
    err_plain = pop.tree_rel_err(plain.synced_v, honest.synced_v)
    err_robust = pop.tree_rel_err(robust.synced_v, honest.synced_v)
    assert err_robust < 0.5 * err_plain, (err_robust, err_plain)
    _finite_tree(robust.synced_v)


def test_robust_round0_hetero_bounds_scale_attack():
    """Round 0 runs per-client SVD bases (the adaptive refresh): the robust
    modes must already bound the attack there via transfer-Gram re-basing
    — the round where the old fallback degraded to median-norm clips."""
    honest, plain = _engine(), _engine()
    robust = _engine(robust_agg="geomedian")
    attack = np.ones(4, np.float32)
    attack[1] = 100.0
    b = _round_batches(0)
    honest.run_round(b)
    plain.run_round(b, attack=attack)
    robust.run_round(b, attack=attack)
    err_plain = pop.tree_rel_err(plain.global_trainable,
                                 honest.global_trainable)
    err_robust = pop.tree_rel_err(robust.global_trainable,
                                  honest.global_trainable)
    assert err_robust < 0.1 * err_plain, (err_robust, err_plain)
    _finite_tree(robust.global_trainable)
    _finite_tree(robust.synced_v)


# -------------------------------------------------- seeded attack schedule --

def test_corruption_schedule_matches_per_round_multipliers():
    """corruption_schedule is exactly the per-round corruption_multipliers
    sequence (the shared operand source for engine/runtime parity grids),
    and start_round windows align with the full schedule."""
    pcfg = pop.ParticipationConfig(corrupt_rate=0.5, corrupt_modes=("scale",),
                                   attack_scale=37.0, seed=3)
    sched = pop.corruption_schedule(pcfg, 4, 6)
    assert len(sched) == 6
    for k, m in enumerate(sched):
        ref = pop.corruption_multipliers(pop.sample_cohort(pcfg, 4, k), pcfg)
        if ref is None:
            assert m is None
        else:
            np.testing.assert_array_equal(m, ref)
    assert any(m is not None for m in sched)
    tail = pop.corruption_schedule(pcfg, 4, 3, start_round=3)
    for a, b in zip(sched[3:], tail):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(a, b)


# ----------------------------------------------- runtime attack parity ------

def test_sharded_runtime_all_ones_attack_short_circuits():
    """run_round(attack=ones) must canonicalize onto the plain program:
    bit-identical outputs and no guarded compile."""
    from repro.fedsim import ShardedFederation

    c = 3
    cfg, mesh, spec, batches = _runtime_setup(c)
    fed_a = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive")
    fed_p = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive")
    for r in range(2):
        b = batches(r)
        ma = fed_a.run_round(b, attack=np.ones(c, np.float32))
        mp = fed_p.run_round(b)
        assert jnp.array_equal(ma["losses"], mp["losses"])
    _leaves_equal(fed_a.global_trainable, fed_p.global_trainable)
    assert fed_a._round_masked is None      # guarded program never built


@pytest.mark.parametrize("attack_val", [np.nan, 1e4], ids=["nan", "scale"])
def test_sharded_runtime_quarantine_matches_masked_round(attack_val):
    """Runtime attack parity with the engine's contract: a quarantined
    attacker ~ the same client masked out of the round."""
    from repro.fedsim import ShardedFederation

    c = 3
    cfg, mesh, spec, batches = _runtime_setup(c)
    kw = dict(state_sync="ajive", quarantine=True, quarantine_zmax=50.0)
    fed_a = ShardedFederation(cfg, spec, mesh, c, **kw)
    fed_m = ShardedFederation(cfg, spec, mesh, c, **kw)
    attack = np.ones(c, np.float32)
    attack[1] = attack_val
    mask = np.ones(c, bool)
    mask[1] = False
    for r in range(2):
        b = batches(r)
        fed_a.run_round(b, attack=attack)
        fed_m.run_round(b, mask=mask)
    _finite_tree(fed_a.global_trainable)
    for la, lb in zip(jax.tree_util.tree_leaves(fed_a.global_trainable),
                      jax.tree_util.tree_leaves(fed_m.global_trainable)):
        assert jnp.allclose(la, lb, atol=1e-5), float(
            jnp.max(jnp.abs(la - lb)))


def test_sharded_runtime_robust_sync_bounds_scale_attack():
    """Runtime robust-𝒮 parity with the engine: under a scale attack the
    trimmed-mean federation tracks the honest trajectory closer than the
    undefended one, and stays finite."""
    from repro.fedsim import ShardedFederation

    c = 3
    cfg, mesh, spec, batches = _runtime_setup(c)
    honest = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive")
    plain = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive")
    robust = ShardedFederation(cfg, spec, mesh, c, state_sync="ajive",
                               robust_agg="trimmed_mean", robust_trim=0.34)
    attack = np.ones(c, np.float32)
    attack[2] = 100.0
    for r in range(2):
        b = batches(r)
        honest.run_round(b)
        plain.run_round(b, attack=attack)
        robust.run_round(b, attack=attack)
    err_plain = pop.tree_rel_err(plain.global_trainable,
                                 honest.global_trainable)
    err_robust = pop.tree_rel_err(robust.global_trainable,
                                  honest.global_trainable)
    assert err_robust < err_plain, (err_robust, err_plain)
    _finite_tree(robust.global_trainable)

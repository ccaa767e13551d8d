"""Parity: the whole-round fused (one-dispatch, donated-buffer) federated
round — rank-r factored client deltas for the GaLore methods — and the
scan-over-rounds driver vs the reference forms in ``core.reference`` (the
eager stage-by-stage round with dense per-client weights and the dense-lift
𝒮), plus chunk-streaming parity and the donation contract."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import galore as gal
from repro.core import reference
from repro.core.fed import METHODS, FedConfig, FedEngine

KEY = jax.random.PRNGKey(5)


def _problem():
    params = {"l1": {"w": 0.3 * jax.random.normal(KEY, (8, 16)),
                     "b": jnp.zeros(16)},
              "l2": {"w": 0.3 * jax.random.normal(jax.random.fold_in(KEY, 1),
                                                  (16, 4)),
                     "b": jnp.zeros(4)}}

    def loss(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["l1"]["w"] + p["l1"]["b"])
        out = h @ p["l2"]["w"] + p["l2"]["b"]
        return jnp.mean((out - y) ** 2)

    return params, loss


def _round_batches(seed, k_rounds=None, k=4, t=5, b=16):
    kb = jax.random.PRNGKey(seed)
    lead = (k, t) if k_rounds is None else (k_rounds, k, t)
    x = jax.random.normal(kb, lead + (b, 8))
    w_true = 0.5 * jax.random.normal(jax.random.fold_in(kb, 1), (8, 4))
    y = jnp.einsum("...bi,io->...bo", x, w_true)
    return (x, y)


def _trees_close(a, b, atol):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.allclose(la, lb, atol=atol), float(
            jnp.max(jnp.abs(la - lb)))


def _trees_rel_close(a, b, rtol):
    """Leaf by leaf, max |a - b| <= rtol · max |b| (a norm-wise relative
    bound, so leaves near zero do not need an absolute floor)."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        err = float(jnp.max(jnp.abs(x - y)))
        assert err <= rtol * float(jnp.max(jnp.abs(y))), (err, rtol)


@pytest.mark.parametrize("method", sorted(METHODS))
def test_fused_round_matches_eager_reference(method):
    """3 rounds of the fused round (factored client deltas for the GaLore
    methods) vs ``reference.run_round_eager`` (separately dispatched
    InitState / 𝒯 / 𝒜 / 𝒮, dense per-client weights, dense-lift 𝒮), for
    every fed method, with weight_decay > 0 (the scaled-base decay path) and
    the adaptive round-0 heterogeneous-basis case (round 0 is in the window).
    flora / fr_lora additionally exercise the frozen-mutating (lift) round
    variant, whose fused program threads the frozen base through its
    outputs."""
    params, loss = _problem()
    engines = {}
    for fused in (True, False):
        eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                                  local_steps=5, clip_norm=10.0,
                                  weight_decay=0.01),
                        loss, params)
        run = eng.run_round if fused else (
            lambda b, eng=eng: reference.run_round_eager(eng, b))
        for r in range(3):
            m = run(_round_batches(r))
            assert jnp.all(jnp.isfinite(m["local_loss"]))
        engines[fused] = eng
    _trees_close(engines[True].global_trainable,
                 engines[False].global_trainable, atol=1e-5)
    _trees_close(engines[True].frozen, engines[False].frozen, atol=1e-5)
    if engines[False].synced_v is not None:
        _trees_close(engines[True].synced_v, engines[False].synced_v,
                     atol=1e-5)
    else:
        assert engines[True].synced_v is None


@pytest.mark.parametrize("method", ["fedgalore", "fedgalore_minus",
                                    "fedgalore_avg_svd"])
def test_factored_clients_match_dense_fused_round(method):
    """The rank-r factored client memory model vs dense per-client weights
    (``reference.run_round_eager``): 3 rounds covering the adaptive round-0
    per-client-basis aggregation and weight_decay > 0 (decay carried by the
    scalar base_scale instead of the dense buffer), every round's local
    losses included."""
    params, loss = _problem()
    engines = {}
    losses = {}
    for factored in (True, False):
        eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                                  local_steps=5, clip_norm=10.0,
                                  weight_decay=0.01),
                        loss, params)
        assert eng._factored
        run = eng.run_round if factored else (
            lambda b, eng=eng: reference.run_round_eager(eng, b))
        losses[factored] = [run(_round_batches(r))["local_loss"]
                            for r in range(3)]
        engines[factored] = eng
    for la, lb in zip(losses[True], losses[False]):
        assert jnp.allclose(la, lb, atol=1e-5)
    _trees_close(engines[True].global_trainable,
                 engines[False].global_trainable, atol=1e-5)
    if engines[False].synced_v is not None:
        _trees_close(engines[True].synced_v, engines[False].synced_v,
                     atol=1e-5)


# Chunked vs one-chunk rounds: twice the worst relative error of 20
# repeated CPU runs, the headroom for other CPUs' code generation. The
# engine's toy reads 1.05e-6 (fedgalore's synced ṽ at one client a chunk);
# the runtime's smoke transformer 8.7e-5 after two rounds, where early-step
# Adam (√v̂ ≈ eps) amplifies the first round's ~1e-6.
CHUNK_RTOL = 2e-6
RUNTIME_CHUNK_RTOL = 2e-4


@pytest.mark.parametrize("method,chunk", [("fedgalore", 2),
                                          ("fedgalore", 1),
                                          ("fedavg_full", 2),
                                          ("fedit", 2)])
def test_chunked_round_bit_identical(method, chunk):
    """Cohort chunk streaming (client_chunk=B < C) must reproduce the
    single-chunk round (B=C): per-client work is independent and 𝒜/𝒮 run
    once on the full reassembled stacks, so the chunk size changes peak
    memory and not the result. Covers the factored (fedgalore), dense
    (fedavg_full), and LoRA (fedit) client models.

    Held to a relative 1e-6 and not to bit-identity: the chunked and the
    single-chunk rounds are differently fused programs, and XLA
    re-associates their fp32 reductions differently (the single-client
    chunk of fedgalore differs from the one-chunk round by ~3e-8 in its
    weights and 1e-6 relative in its synced ṽ)."""
    params, loss = _problem()
    engines = {}
    for c in (None, chunk):
        eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                                  local_steps=5, clip_norm=10.0,
                                  weight_decay=0.01, client_chunk=c),
                        loss, params)
        for r in range(2):
            eng.run_round(_round_batches(r))
        engines[c] = eng
    _trees_rel_close(engines[chunk].global_trainable,
                     engines[None].global_trainable, rtol=CHUNK_RTOL)
    if engines[None].synced_v is not None:
        _trees_rel_close(engines[chunk].synced_v, engines[None].synced_v,
                         rtol=CHUNK_RTOL)


def test_client_chunk_must_divide_cohort():
    params, loss = _problem()
    eng = FedEngine(FedConfig(method="fedgalore", rank=4, local_steps=5,
                              client_chunk=3), loss, params)
    with pytest.raises(ValueError, match="must divide"):
        eng.run_round(_round_batches(0))


def test_factored_buffers_smaller_than_dense():
    """The persistent client buffers of the factored round are the rank-r
    accumulators — strictly smaller than the dense (C, m, n) weight stacks
    they replace (the C≈512 scaling lever): the same optimizer states plus
    one dense copy of the trainables per client."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                              local_steps=5), loss, params)
    batches = _round_batches(0)
    k_clients = jax.tree_util.tree_leaves(batches)[0].shape[0]
    eng.run_round(batches)
    assert eng._factored
    opt_bytes = sum(x.nbytes
                    for x in jax.tree_util.tree_leaves(eng._client_opt))
    dense_bytes = opt_bytes + k_clients * sum(
        x.nbytes for x in jax.tree_util.tree_leaves(eng.global_trainable))
    assert 0 < eng.client_buffer_bytes() < dense_bytes


@pytest.mark.parametrize("method", ["fedgalore", "fr_lora"])
def test_scan_over_rounds_matches_per_round(method):
    """run_rounds (K rounds, ONE dispatch) ≡ K fused run_round calls —
    fr_lora covers the frozen-in-carry scan variant."""
    params, loss = _problem()
    eng_a = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                                local_steps=5), loss, params)
    eng_b = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                                local_steps=5), loss, params)
    rb = _round_batches(0, k_rounds=4)
    m = eng_a.run_rounds(rb)
    assert m["local_loss"].shape == (4, 4, 5)
    for r in range(4):
        mb = eng_b.run_round(jax.tree_util.tree_map(lambda x: x[r], rb))
        assert jnp.allclose(m["local_loss"][r], mb["local_loss"], atol=1e-6)
    _trees_close(eng_a.global_trainable, eng_b.global_trainable, atol=1e-6)
    _trees_close(eng_a.frozen, eng_b.frozen, atol=1e-6)
    _trees_close(eng_a.synced_v, eng_b.synced_v, atol=1e-6)
    assert eng_a.round_idx == eng_b.round_idx == 4


def test_donated_buffers_second_round_ok():
    """The fused round donates the stacked (C, …) client buffers; the engine
    must adopt each round's outputs so the next call never touches a donated
    (deleted) array. Also: run_round after run_rounds stays consistent."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                              local_steps=5), loss, params)
    m0 = eng.run_round(_round_batches(0))
    m1 = eng.run_round(_round_batches(1))       # reuses donated buffers
    assert jnp.isfinite(m1["mean_final_loss"])
    eng.run_rounds(_round_batches(2, k_rounds=2))
    m3 = eng.run_round(_round_batches(3))       # back to the donated path
    assert jnp.isfinite(m3["mean_final_loss"])
    assert eng.round_idx == 5
    assert m0["mean_final_loss"] != m1["mean_final_loss"]


def test_fused_round_single_dispatch_program():
    """The whole round — InitState, T local steps, 𝒜, 𝒮 — must lower as one
    jitted call: after warmup, a round triggers no new trace."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                              local_steps=5), loss, params)
    eng.run_round(_round_batches(0))    # round-0 trace (no synced_v)
    eng.run_round(_round_batches(1))    # steady-state trace (with synced_v)
    traced = eng._round_jitted()._cache_size()
    eng.run_round(_round_batches(2))
    assert eng._round_jitted()._cache_size() == traced


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


def test_sharded_runtime_fused_matches_eager():
    """ShardedFederation: the in-mesh 𝒮 of the fused round must reproduce
    the dense-lift reference 𝒮 (``reference.sync_client_states_dense``)
    applied to the runtime's own uplinked states — the 𝒯→𝒜 program's
    (``state_sync=None``) raw end-of-round states — round for round, and
    the scan driver over the same two rounds must match that per-round
    dispatch."""
    from repro.fedsim import ShardedFederation
    from repro.launch import steps as steps_lib

    c_clients = 3
    cfg, mesh, spec, batches = _runtime_setup(c_clients)

    fed = ShardedFederation(cfg, spec, mesh, c_clients, state_sync="ajive")
    raw_step = jax.jit(steps_lib.make_fed_round_step(cfg, spec, c_clients))
    ref_sync = jax.jit(lambda st, w: reference.sync_client_states_dense(
        st, w, c_clients, "ajive"))
    w = jnp.full((c_clients,), 1.0 / c_clients)
    for r in range(2):
        b = batches(r)
        with jax.set_mesh(mesh):
            raw_global, up, raw_losses, _ = raw_step(
                fed.global_trainable, fed.frozen, fed.opt_states, b, w)
            want = ref_sync(up, w)
        mf = fed.run_round(b)
        assert jnp.allclose(mf["losses"], raw_losses, atol=1e-6)
        _trees_close(fed.global_trainable, raw_global, atol=1e-6)
        _trees_close(fed.opt_states, want, atol=1e-5)

    fed_s = ShardedFederation(cfg, spec, mesh, c_clients, state_sync="ajive")
    ms = fed_s.run_rounds(jax.tree_util.tree_map(
        lambda *x: jnp.stack(x), batches(0), batches(1)))
    assert ms["losses"].shape == (2, c_clients, 2)
    _trees_close(fed_s.global_trainable, fed.global_trainable, atol=1e-6)


def _dense_reference_rounds(cfg, spec, mesh, fed, batch_list, state_sync):
    """``reference.make_dense_round_step`` from fed's current states, one
    round per batch; returns (global trainable, states, losses per round).
    Run it before fed's own rounds: those donate fed's buffers."""
    step = jax.jit(reference.make_dense_round_step(cfg, spec, fed.n_clients,
                                                   state_sync))
    g, st = fed.global_trainable, fed.opt_states
    w = jnp.ones((fed.n_clients,), jnp.float32)
    losses = []
    with jax.set_mesh(mesh):
        for b in batch_list:
            g, st, loss = step(g, fed.frozen, st, b, w)
            losses.append(loss)
    return g, st, losses


def test_sharded_runtime_factored_matches_dense_clients():
    """The runtime's factored (lift-free) client round vs dense per-client
    weight stacks (``reference.make_dense_round_step``: dense gradients, the
    weighted mean as 𝒜, the dense-lift 𝒮): ≤5e-4 on the global trainable
    and the synced optimizer states after 2 rounds, with the production
    weight_decay > 0 riding the scaled base. Tolerance is fp noise, not a
    representation gap: with the real (nb, m, n) projection weights
    trained, early-step Adam (rsqrt of near-zero v) amplifies
    reduction-order differences between the mathematically identical
    paths past 1e-5: a 7e-9 single-step difference reaches ~2e-4 by round
    2 through coordinates where √v̂ ≈ eps (each step stays lr-bounded, so
    the drift is noise-shaped, not divergent). Losses stay 1e-5-tight."""
    from repro.fedsim import ShardedFederation

    c_clients = 3
    cfg, mesh, spec, batches = _runtime_setup(c_clients)
    assert spec.weight_decay > 0

    fed = ShardedFederation(cfg, spec, mesh, c_clients, state_sync="ajive")
    bl = [batches(r) for r in range(2)]
    ref_g, ref_st, ref_losses = _dense_reference_rounds(
        cfg, spec, mesh, fed, bl, "ajive")
    for b, lr in zip(bl, ref_losses):
        mf = fed.run_round(b)
        assert jnp.allclose(mf["losses"], lr, atol=1e-5)
    _trees_close(fed.global_trainable, ref_g, atol=5e-4)
    _trees_close(fed.opt_states, ref_st, atol=5e-4)


def test_sharded_runtime_chunked_bit_identical():
    """client_chunk=B < C must reproduce the single-chunk round in the
    sharded runtime too (same per-client programs, 𝒜/𝒮 on the full
    reassembled stacks). Held to the relative ``RUNTIME_CHUNK_RTOL`` and
    not to bit-identity: the chunked and single-chunk round programs are
    fused differently, and XLA re-associates their fp32 reductions
    differently."""
    from repro.fedsim import ShardedFederation

    c_clients = 4
    cfg, mesh, spec, batches = _runtime_setup(c_clients)

    feds = {c: ShardedFederation(cfg, spec, mesh, c_clients,
                                 state_sync="ajive", client_chunk=c)
            for c in (None, 2)}
    for r in range(2):
        b = batches(r)
        feds[None].run_round(b)
        feds[2].run_round(b)
    _trees_rel_close(feds[2].global_trainable, feds[None].global_trainable,
                     rtol=RUNTIME_CHUNK_RTOL)
    _trees_rel_close(feds[2].opt_states, feds[None].opt_states,
                     rtol=RUNTIME_CHUNK_RTOL)


def test_sharded_runtime_svd_mode_hetero_sync_matches_dense_oracle():
    """refresh_mode='svd' diverges the client bases, so the factored
    clients' 𝒜 contracts per-client lifts and the in-mesh 𝒮 takes the
    heterogeneous-basis factored path (r×r transfer Grams). The round's 𝒜
    and uplink must agree with the dense per-client round
    (``reference.make_dense_round_step``), and 𝒮 on the runtime's own
    uplinked states with the dense per-client lift
    (``reference.sync_client_states_dense``), to fp32 precision."""
    from repro.configs import get_config, smoke_variant
    from repro.fedsim import ShardedFederation
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import TrainSpec

    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    mesh = make_host_mesh(1)
    spec = TrainSpec(rank=4, lr=1e-3, local_steps=2, refresh_mode="svd",
                     refresh_every=2)
    kk = jax.random.PRNGKey(3)
    toks = jax.random.randint(kk, (3, 2, 2, 8), 0, cfg.vocab_size)
    b = {"tokens": toks, "labels": toks}

    # A state_sync='none' federation's round leaves the uplinked states
    # in place (its 𝒮 only bumps the seed): one round program, no second.
    fed = ShardedFederation(cfg, spec, mesh, 3, state_sync="none")
    ref_g, ref_up, (ref_losses,) = _dense_reference_rounds(
        cfg, spec, mesh, fed, [b], "none")
    m = fed.run_round(b)
    assert jnp.allclose(m["losses"], ref_losses, atol=1e-5)
    _trees_close(fed.global_trainable, ref_g, atol=1e-5)
    _trees_close(fed.opt_states, ref_up, atol=1e-5)
    up, w = fed.opt_states, jnp.full((3,), 1.0 / 3)
    bases = jax.tree_util.tree_leaves(
        gal.extract_bases(gal.galore_state_of(up)))
    assert not jnp.allclose(bases[0][0], bases[0][1], atol=1e-3)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda st: steps_lib.sync_client_states(
            st, w, 3, "ajive", bases_shared=False))(up)
        want = jax.jit(lambda st: reference.sync_client_states_dense(
            st, w, 3, "ajive"))(up)
    for a, d in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert jnp.allclose(a.astype(jnp.float32), d.astype(jnp.float32),
                            atol=1e-5)

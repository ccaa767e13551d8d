"""Lift-free factored rounds: the delta-context forward (split-matmul
weight read), the projected-cotangent VJP (gradients arrive in rank-r
coordinates, clipping via exact dense-norm probes), kernel-vs-reference
parity, engine/runtime lift-free ≡ transient-lift parity of the local phase
for all GaLore methods, the jaxpr shape probe (zero dense m×n lift GEMMs /
gradient cotangents), and LoRA methods' indifference to the delta
context."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import galore as gal
from repro.core import projector as proj
from repro.core.fed import METHODS, FedConfig, FedEngine
from repro.kernels import ops as kops
from repro.kernels.ref import lowrank_linear_ref
from repro.models import layers

KEY = jax.random.PRNGKey(11)

GALORE_METHODS = [m for m, s in METHODS.items()
                  if s.optimizer == "galore_adamw"]
LORA_METHODS = ["fedit", "ffa_lora", "lora_fair"]


# ------------------------------------------------------------- kernel -------

@pytest.mark.parametrize("side,shape,r", [
    ("right", (16, 8), 3),          # m >= n: basis (n, r), rt (m, r)
    ("left", (8, 16), 3),           # m < n:  basis (m, r), rt (r, n)
    ("right", (33, 16), 4),         # odd row count: masked tail tile
    ("left", (16, 33), 4),
])
def test_lowrank_linear_kernel_matches_ref(side, shape, r):
    m, n = shape
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (5, m))
    w = jax.random.normal(ks[1], (m, n))
    basis = jax.random.normal(ks[2], ((n if side == "right" else m), r))
    rt = jax.random.normal(ks[3], ((m, r) if side == "right" else (r, n)))
    got = kops.lowrank_linear(x, w, basis, rt, 0.9, side=side, block_rows=8)
    want = lowrank_linear_ref(x, w, basis, rt, 0.9, side=side)
    assert jnp.allclose(got, want, atol=1e-5), float(
        jnp.max(jnp.abs(got - want)))


def test_lowrank_linear_kernel_leading_dims_and_side_inference():
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (2, 3, 12))          # (..., t, m)
    w = jax.random.normal(ks[1], (12, 6))
    basis = jax.random.normal(ks[2], (6, 2))
    rt = jax.random.normal(ks[3], (12, 2))
    got = kops.lowrank_linear(x, w, basis, rt, 1.0)   # side inferred: right
    want = lowrank_linear_ref(x, w, basis, rt, 1.0, side="right")
    assert got.shape == (2, 3, 6)
    assert jnp.allclose(got, want, atol=1e-5)


def _rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("side,r,tokens,clients", [
    ("right", 3, 32, None), ("left", 3, 32, None),
    ("right", 8, 32, None), ("left", 8, 32, None),
    ("right", 16, 32, None), ("left", 16, 32, None),   # 9r > 128: 2 passes
    ("right", 8, 37, None), ("left", 8, 37, None),     # masked tail tile
    ("right", 8, 37, 3), ("left", 16, 37, 3),           # vmapped clients
])
def test_lowrank_linear_bf16_split_words_exact(side, r, tokens, clients):
    """Bf16 activations take the split-word path: it matches the fp32
    reference on fp32-upcast operands, and its delta is at least 100× closer
    to it than the same apply with the factors rounded to bf16 — the words
    carry the whole fp32 factor, not a bf16 truncation. The base weight is
    fp32 only so that the kernel returns fp32 (the result dtype is the base
    GEMM's); ``scale = 0`` leaves the delta alone in the output."""
    m, n = (48, 24) if side == "right" else (24, 48)
    c = clients or 1
    ks = jax.random.split(jax.random.fold_in(KEY, r), 4)
    x = jax.random.normal(ks[0], (c, tokens, m)).astype(jnp.bfloat16)
    w = 0.05 * jax.random.normal(ks[1], (m, n))
    bdim = n if side == "right" else m
    basis = jnp.linalg.qr(jax.random.normal(ks[2], (c, bdim, r)))[0]
    # a round's delta: |R̃| ~ 1e-3 of |w|
    rt = 5e-5 * jax.random.normal(ks[3], (c,) + ((m, r) if side == "right"
                                                  else (r, n)))

    def each(fn):
        if clients is None:
            return lambda b, rt, s: fn(x[0], b[0], rt[0], s)
        return lambda b, rt, s: jax.vmap(fn, in_axes=(0, 0, 0, None))(
            x, b, rt, s)

    kernel = each(lambda x, b, rt, s: kops.lowrank_linear(
        x, w, b, rt, s, side=side, block_rows=16))
    ref = each(lambda x, b, rt, s: lowrank_linear_ref(
        x.astype(jnp.float32), w, b, rt, s, side=side))
    assert _rel_err(kernel(basis, rt, 0.97), ref(basis, rt, 0.97)) <= 1e-5

    delta = ref(basis, rt, 0.0)
    err = _rel_err(kernel(basis, rt, 0.0), delta)
    q = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    err_bf16 = _rel_err(ref(q(basis), q(rt), 0.0), delta)
    assert err <= 1e-5 and err <= err_bf16 / 100, (err, err_bf16)


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation in ``jaxpr`` and the jaxprs nested in
    its equations' parameters (jit, pallas_call, cond branches)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _dot_generals(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_lowrank_linear_path_follows_x_dtype(dtype):
    """The dtype of ``x`` is the only switch: the kernel traced for bf16
    activations holds no fp32 dot and no ``HIGHEST`` contraction; the one
    traced for fp32 activations keeps them."""
    x = jnp.zeros((2, 40, 32), dtype)
    w = jnp.zeros((32, 16), dtype)
    basis, rt = jnp.zeros((16, 8)), jnp.zeros((32, 8))
    jaxpr = jax.make_jaxpr(lambda x, w, b, rt: kops.lowrank_linear(
        x, w, b, rt, 1.0, side="right", block_rows=16))(x, w, basis, rt)
    dots = list(_dot_generals(jaxpr.jaxpr))
    fp32 = [e for e in dots
            if any(v.aval.dtype == jnp.float32 for v in e.invars)
            or jax.lax.Precision.HIGHEST in (e.params["precision"] or ())]
    if dtype == jnp.bfloat16:
        assert len(dots) >= 3 and not fp32, [e.params for e in fp32]
    else:
        assert fp32


def test_lowrank_linear_ref_equals_materialized_weight():
    """The split matmul IS x @ (scale·W + lift) — per side."""
    for side, (m, n) in (("right", (10, 6)), ("left", (6, 10))):
        ks = jax.random.split(jax.random.fold_in(KEY, ord(side[0])), 4)
        x = jax.random.normal(ks[0], (4, m))
        w = jax.random.normal(ks[1], (m, n))
        basis = jax.random.normal(ks[2], ((n if side == "right" else m), 3))
        rt = jax.random.normal(ks[3], ((m, 3) if side == "right" else (3, n)))
        lifted = (rt @ basis.T if side == "right" else basis @ rt)
        want = x @ (0.7 * w + lifted)
        got = lowrank_linear_ref(x, w, basis, rt, 0.7, side=side)
        assert jnp.allclose(got, want, atol=1e-4)


# -------------------------------------------- projected-cotangent VJP -------

@pytest.mark.parametrize("side,shape", [("right", (12, 7)),
                                        ("left", (7, 12))])
def test_liftfree_vjp_matches_transient_ad(side, shape):
    """grad wrt R̃ through the delta context == project(dense grad, B) from
    AD through the materialized weight, and the norm-probe cotangent is the
    exact squared dense-gradient norm — per side."""
    m, n = shape
    r = 3
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (6, m))
    w = jax.random.normal(ks[1], (m, n))
    basis = jax.random.normal(ks[2], ((n if side == "right" else m), r))
    rt = 0.1 * jax.random.normal(ks[3], ((m, r) if side == "right"
                                         else (r, n)))
    tgt = jax.random.normal(ks[4], (6, n))
    scale = jnp.asarray(0.95)

    def loss_liftfree(rt, nsq):
        y = layers.lowrank_apply(side, False, x, w, basis, rt, nsq, scale)
        return jnp.sum(jnp.tanh(y - tgt))

    (drt, dnsq) = jax.grad(loss_liftfree, argnums=(0, 1))(rt, jnp.zeros(()))

    def loss_transient(w_eff):
        return jnp.sum(jnp.tanh(x @ w_eff - tgt))

    lifted = (rt @ basis.T if side == "right" else basis @ rt)
    g_dense = jax.grad(loss_transient)(scale * w + lifted)
    want_drt = proj.project(g_dense, basis, side)
    assert jnp.allclose(drt, want_drt, atol=1e-5), float(
        jnp.max(jnp.abs(drt - want_drt)))
    assert jnp.allclose(dnsq, jnp.sum(g_dense * g_dense), rtol=1e-5)


def test_liftfree_read_vjp_bias_style_leaf():
    """Non-matmul consumption (stacked bias blocks added to activations):
    the leaf-read VJP still returns the projected cotangent and ‖∂y‖²."""
    m, n, r = 2, 9, 2                   # skinny left block, like (nb, d)
    ks = jax.random.split(KEY, 4)
    w = jax.random.normal(ks[0], (m, n))
    basis = jax.random.normal(ks[1], (m, r))
    rt = 0.1 * jax.random.normal(ks[2], (r, n))
    dl = layers.LowRankDelta(w=w, basis=basis, rt=rt, nsq=jnp.zeros(()),
                             scale=jnp.asarray(1.0))
    h = jax.random.normal(ks[3], (4, m, n))

    def loss_of(rt, nsq):
        d = dl._replace(rt=rt, nsq=nsq)
        return jnp.sum(jnp.sin(h + d))          # __radd__ -> read()
    drt, dnsq = jax.grad(loss_of, argnums=(0, 1))(rt, jnp.zeros(()))

    def loss_dense(w_eff):
        return jnp.sum(jnp.sin(h + w_eff))
    g_dense = jax.grad(loss_dense)(w + basis @ rt)
    assert jnp.allclose(drt, proj.project(g_dense, basis, "left"), atol=1e-5)
    assert jnp.allclose(dnsq, jnp.sum(g_dense * g_dense), rtol=1e-5)


def test_sqnorm_gram_tiled_matches_direct():
    """The tiled token-Gram norm probe (t > tile: scanned row tiles with a
    zero-padded tail) equals the single-Gram value and the direct
    ‖xᵀdy‖²."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (37, 5))
    dy = jax.random.normal(ks[1], (37, 4))
    direct = jnp.sum((x.T @ dy) ** 2)
    one_gram = layers._sqnorm_gram(x, dy)
    tiled = layers._sqnorm_gram(x, dy, tile=8)       # 5 tiles, padded tail
    assert jnp.allclose(one_gram, direct, rtol=1e-5)
    assert jnp.allclose(tiled, direct, rtol=1e-5)


def test_dense_is_plain_matmul_for_plain_weights():
    x = jax.random.normal(KEY, (3, 5))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (5, 4))
    assert jnp.array_equal(layers.dense(x, w), x @ w)


# ------------------------------------------------------ engine parity -------

def _problem():
    params = {"l1": {"w": 0.3 * jax.random.normal(KEY, (8, 16)),
                     "b": jnp.zeros(16)},
              "l2": {"w": 0.3 * jax.random.normal(jax.random.fold_in(KEY, 1),
                                                  (16, 4)),
                     "b": jnp.zeros(4)}}

    def loss(p, batch):
        x, y = batch
        # Raw `x @ w` on purpose: LowRankDelta.__rmatmul__ must make
        # arbitrary losses lift-free without edits.
        h = jnp.tanh(x @ p["l1"]["w"] + p["l1"]["b"])
        out = h @ p["l2"]["w"] + p["l2"]["b"]
        return jnp.mean((out - y) ** 2)

    return params, loss


def _round_batches(seed, k=4, t=5, b=6):
    kb = jax.random.PRNGKey(seed)
    x = jax.random.normal(kb, (k, t, b, 8))
    w_true = 0.5 * jax.random.normal(jax.random.fold_in(kb, 1), (8, 4))
    return (x, jnp.einsum("...bi,io->...bo", x, w_true))


def _trees_close(a, b, atol):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert jnp.allclose(la, lb, atol=atol), float(
            jnp.max(jnp.abs(la - lb)))


@pytest.mark.parametrize("method", sorted(GALORE_METHODS))
def test_liftfree_matches_transient_lift_all_galore_methods(method):
    """One client's T local steps, lift-free (``_local_train_liftfree_one``)
    vs the transient-lift read (``_local_train_factored_one``) from the same
    InitState ≤ 1e-5, per GaLore method, with an ACTIVE global-norm clip
    (clip_norm=0.5 — the dense-norm probes must reproduce the dense path's
    clip factor exactly) and weight decay. The InitState is round 1's after
    a real round 0, so it carries the method's synced ṽ and the
    seeded-random refresh the lift-free read serves. The toy covers both
    projection sides (l1 (8,16) left, l2 (16,4) right). Whole rounds against
    the dense reference: test_fed_round_fused.py."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                              local_steps=5, clip_norm=0.5,
                              weight_decay=0.01), loss, params)
    assert eng._lift_free
    eng.run_round(_round_batches(0))
    st0 = eng._init_state0(eng.round_idx, eng.synced_v, eng.global_trainable)
    d0 = gal.zero_client_deltas(gal.galore_state_of(st0))
    batches = jax.tree_util.tree_map(lambda x: x[1], _round_batches(1))
    outs = {}
    for lf, fn in ((True, eng._local_train_liftfree_one),
                   (False, eng._local_train_factored_one)):
        outs[lf] = jax.jit(lambda d, st, b, fn=fn: fn(
            d, st, b, eng.frozen, eng.global_trainable))(d0, st0, batches)
        assert jnp.all(jnp.isfinite(outs[lf][2]))
    _trees_close(outs[True], outs[False], atol=1e-5)


def test_liftfree_scan_over_rounds_matches_per_round():
    """run_rounds drives the lift-free round (incl. the round-0 transient
    cond) identically to per-round dispatch."""
    params, loss = _problem()
    eng_a = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                                local_steps=5), loss, params)
    eng_b = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                                local_steps=5), loss, params)
    rb3 = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), _round_batches(0), _round_batches(1),
        _round_batches(2))
    m = eng_a.run_rounds(rb3)
    for r in range(3):
        mb = eng_b.run_round(_round_batches(r))
        assert jnp.allclose(m["local_loss"][r], mb["local_loss"], atol=1e-6)
    _trees_close(eng_a.global_trainable, eng_b.global_trainable, atol=1e-6)


def _round_primitives(eng, batches):
    """Every primitive name in the traced round program of ``eng``."""
    k = jax.tree_util.tree_leaves(batches)[0].shape[0]
    jaxpr = jax.make_jaxpr(lambda b: eng._round_core(
        eng.global_trainable, eng.frozen, eng.synced_v,
        jnp.asarray(1, jnp.int32), b, eng._normalize_weights(None, k)))(
        batches)
    return _primitive_names(jaxpr.jaxpr, set())


@pytest.mark.parametrize("method", LORA_METHODS + ["fedavg_full"])
def test_lora_and_dense_methods_untouched_by_delta_context(method):
    """The delta context only engages for factored GaLore clients: the
    round program of a LoRA or dense method holds no custom-VJP call (the
    LowRankDelta apply is one; a factored GaLore round, the positive
    control, holds it), and its rounds run."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2,
                              local_steps=3), loss, params)
    assert eng._lift_free is False
    prims = _round_primitives(eng, _round_batches(0, t=3))
    assert not any("custom_vjp" in p for p in prims), prims
    control = FedEngine(FedConfig(method="fedgalore_minus", rank=4,
                                  lr=3e-2, local_steps=3), loss, params)
    assert any("custom_vjp" in p
               for p in _round_primitives(control, _round_batches(0, t=3)))
    for r in range(2):
        m = eng.run_round(_round_batches(r, t=3))
        assert jnp.all(jnp.isfinite(m["local_loss"]))


def test_liftfree_chunked_bit_identical():
    """Chunk streaming composes with the lift-free local phase bit-for-bit."""
    params, loss = _problem()
    engines = {}
    for chunk in (None, 2):
        eng = FedEngine(FedConfig(method="fedgalore", rank=4, lr=3e-2,
                                  local_steps=5, client_chunk=chunk),
                        loss, params)
        for r in range(2):
            eng.run_round(_round_batches(r))
        engines[chunk] = eng
    for la, lb in zip(jax.tree_util.tree_leaves(engines[None].global_trainable),
                      jax.tree_util.tree_leaves(engines[2].global_trainable)):
        assert jnp.array_equal(la, lb)


def test_liftfree_forward_kernel_path_matches_jnp():
    """dense() under lowrank_pallas_override(True) routes the forward
    through the fused Pallas kernel (interpret mode on CPU) — same rounds,
    fp32-close results."""
    params, loss = _problem()
    engines = {}
    for pallas in (True, False):
        with layers.lowrank_pallas_override(pallas):
            eng = FedEngine(FedConfig(method="fedgalore_minus", rank=4,
                                      lr=3e-2, local_steps=3), loss, params)
            for r in range(2):
                eng.run_round(_round_batches(r))
        engines[pallas] = eng
    _trees_close(engines[True].global_trainable,
                 engines[False].global_trainable, atol=1e-5)


# ------------------------------------------------------- jaxpr probe --------

def _dot_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            acc.add(tuple(eqn.outvars[0].aval.shape))
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                _dot_shapes(sub, acc)
    return acc


def _primitive_names(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                _primitive_names(sub, acc)
    return acc


def _as_jaxprs(v):
    if hasattr(v, "jaxpr") and hasattr(v, "consts"):    # ClosedJaxpr
        return [v.jaxpr]
    if hasattr(v, "eqns"):                              # Jaxpr
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for x in v:
            out.extend(_as_jaxprs(x))
        return out
    return []


def _local_step_dot_shapes(lift_free: bool):
    """All dot_general output shapes in ONE compiled local training phase
    (the T-step scan for one client) of the factored round, lift-free
    (``_local_train_liftfree_one``) or with the transient-lift read
    (``_local_train_factored_one``). rank=3 keeps
    every projected-space shape (m,3)/(3,n) distinct from the dense (m,n)
    target shapes the probe asserts on."""
    params, loss = _problem()
    eng = FedEngine(FedConfig(method="fedgalore_minus", rank=3, lr=3e-2,
                              local_steps=2, clip_norm=0.5,
                              weight_decay=0.01), loss, params)
    st0 = eng._init_state0(jnp.asarray(1, jnp.int32), None,
                           eng.global_trainable)
    d0 = gal.zero_client_deltas(gal.galore_state_of(st0))
    batches = jax.tree_util.tree_map(lambda x: x[0], _round_batches(0, t=2))
    fn = (eng._local_train_liftfree_one if lift_free
          else eng._local_train_factored_one)
    jaxpr = jax.make_jaxpr(
        lambda d, s, b: fn(d, s, b, eng.frozen, eng.global_trainable))(
        d0, st0, batches)
    return _dot_shapes(jaxpr.jaxpr, set())


def test_liftfree_local_step_has_no_dense_mn_gemm():
    """The acceptance probe: the lift-free local phase lowers ZERO
    dot_generals with a dense (m, n) target-leaf output — no lift GEMM, no
    dense gradient cotangent, no dense projection. The transient-lift read
    (positive control) lowers several."""
    target_shapes = {(8, 16), (16, 4)}          # the toy's target leaves
    lf = _local_step_dot_shapes(lift_free=True)
    assert not (lf & target_shapes), lf & target_shapes
    transient = _local_step_dot_shapes(lift_free=False)
    assert transient & target_shapes            # the transient read lifts


# ------------------------------------------------------ runtime parity ------

def test_sharded_runtime_liftfree_matches_transient():
    """The runtime's lift-free local phase (``steps.client_round_liftfree``,
    the default client round) vs its transient-lift read
    (``steps.client_round_factored``) on one client of the smoke
    transformer, from the same round-start state: same per-step losses and
    ≤5e-4 agreement of the accumulators and optimizer states after T=2
    steps. The two formulations are mathematically identical; early-step
    Adam (√v̂ ≈ eps coordinates) amplifies reduction-order noise — each
    step stays lr-bounded, so the drift is noise-shaped, not divergent."""
    from repro.configs import get_config, smoke_variant
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import TrainSpec
    from repro.models.layers import batch_axes_override

    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    mesh = make_host_mesh(1)
    spec = TrainSpec(rank=4, lr=1e-3, local_steps=2, refresh_mode="random")
    trainable, frozen, opt_state = steps_lib.init_train_state(
        jax.random.PRNGKey(0), cfg, spec)
    d0 = gal.zero_client_deltas(gal.galore_state_of(opt_state))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 8), 0,
                              cfg.vocab_size)
    batches = {"tokens": toks, "labels": toks}
    outs = {}
    with jax.set_mesh(mesh), batch_axes_override(()):
        for lf, fn in ((True, steps_lib.client_round_liftfree),
                       (False, steps_lib.client_round_factored)):
            outs[lf] = jax.jit(lambda d, st, b, fn=fn: fn(
                cfg, spec, d, frozen, st, b, trainable))(d0, opt_state,
                                                        batches)
    assert jnp.allclose(outs[True][2], outs[False][2], atol=1e-4)
    for la, lb in zip(jax.tree_util.tree_leaves(outs[True]),
                      jax.tree_util.tree_leaves(outs[False])):
        assert jnp.allclose(la.astype(jnp.float32), lb.astype(jnp.float32),
                            atol=5e-4)

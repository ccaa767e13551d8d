import jax
import jax.numpy as jnp
import pytest

from repro.core import projector as proj
from repro.core import reference
from repro.core import state_sync as sync


@pytest.fixture
def block():
    key = jax.random.PRNGKey(0)
    k, n, m, r = 5, 24, 16, 4
    basis = proj.random_basis(0, n, r)                 # shared (seeded) basis
    # ground-truth lifted second moment with shared structure
    shared = jnp.abs(jax.random.normal(key, (m, n)))
    v_stack = []
    for i in range(k):
        ki = jax.random.fold_in(key, i)
        drift = 0.5 * jnp.abs(jax.random.normal(ki, (m, n)))
        v_stack.append((shared + drift) @ basis)       # projected view (m, r)
    return jnp.stack(v_stack), basis, shared


def test_lift_views_shapes(block):
    v_stack, basis, _ = block
    views = sync.lift_views(v_stack, basis, proj.RIGHT)
    assert views.shape == (5, 16, 24)


def test_sync_none(block):
    v_stack, basis, _ = block
    assert sync.sync_block("none", v_stack, basis, basis, proj.RIGHT) is None


def test_sync_avg_is_mean(block):
    v_stack, basis, _ = block
    out = sync.SYNC_PROTOCOLS["avg"](v_stack, basis, proj.RIGHT)
    manual = jnp.mean(sync.lift_views(v_stack, basis, proj.RIGHT), axis=0)
    assert jnp.allclose(out, manual, atol=1e-5)


@pytest.mark.parametrize("protocol", ["avg", "avg_svd", "ajive"])
def test_sync_block_end_to_end(block, protocol):
    v_stack, basis, _ = block
    new_basis = proj.random_basis(1, 24, 4)
    out = sync.sync_block(protocol, v_stack, basis, new_basis, proj.RIGHT,
                          rank=4)
    assert out.shape == v_stack.shape[1:]
    assert float(jnp.min(out)) >= 0.0          # ṽ init must stay non-negative
    assert not bool(jnp.any(jnp.isnan(out)))


def test_left_side_roundtrip():
    key = jax.random.PRNGKey(1)
    k, m, n, r = 3, 8, 24, 4                   # left block: m < n
    basis = proj.random_basis(0, m, r)
    v_stack = jnp.abs(jax.random.normal(key, (k, r, n)))
    views = sync.lift_views(v_stack, basis, proj.LEFT)
    assert views.shape == (k, m, n)
    back = sync.project_state(views[0], basis, proj.LEFT)
    assert back.shape == (r, n)


# ------------------------------------------------------ factored fast path --

def _structured_stack(key, side, k=5, m=16, n=24, r=4):
    """Projected moments with a graded shared signal (well-separated spectrum
    so the dense and factored joint projectors agree to fp32 precision)."""
    scale = jnp.linspace(5.0, 2.0, r)
    shape = (m, r) if side == proj.RIGHT else (r, n)
    base = jax.random.normal(key, shape) * (
        scale[None, :] if side == proj.RIGHT else scale[:, None])
    return jnp.stack([jnp.abs(base + 0.2 * jax.random.normal(
        jax.random.fold_in(key, i), shape)) for i in range(k)])


@pytest.mark.parametrize("side", [proj.RIGHT, proj.LEFT])
@pytest.mark.parametrize("protocol", ["avg", "avg_svd", "ajive"])
def test_sync_block_factored_matches_dense(side, protocol):
    """sync_block_factored == sync_block (lift → 𝒮 → re-project oracle) for
    every protocol, both sides, including the old→new basis transfer."""
    r, dim = 4, 24
    v_stack = _structured_stack(jax.random.PRNGKey(0), side, r=r)
    old_b = proj.random_basis(0, dim, r)
    new_b = proj.random_basis(1, dim, r)
    w = jnp.array([1, 2, 1, 1, 3.0])
    dense = sync.sync_block(protocol, v_stack, old_b, new_b, side,
                            weights=w, rank=r)
    fact = sync.sync_block_factored(protocol, v_stack, old_b, new_b, side,
                                    weights=w, rank=r)
    assert fact.shape == dense.shape
    assert jnp.allclose(fact, dense, atol=1e-5)
    assert float(jnp.min(fact)) >= 0.0


def test_sync_block_factored_none():
    v_stack = _structured_stack(jax.random.PRNGKey(0), proj.RIGHT)
    b = proj.random_basis(0, 24, 4)
    assert sync.sync_block_factored("none", v_stack, b, b, proj.RIGHT) is None


def test_synced_factored_projected_shape():
    """sync_block_synced_factored returns the round-k-basis projected state
    (the uplink shape) — no ambient dimension anywhere."""
    v_stack = _structured_stack(jax.random.PRNGKey(2), proj.RIGHT)
    out = sync.sync_block_synced_factored("ajive", v_stack, proj.RIGHT,
                                          rank=4)
    assert out.shape == v_stack.shape[1:]


# ------------------------------------------ heterogeneous-basis factored ----

def _hetero_bases(key, k, dim, r):
    """Per-client orthonormal bases that genuinely diverge (the adaptive
    round-0 / svd-refresh case)."""
    return jnp.stack([proj.random_basis(jax.random.fold_in(key, i), dim, r)
                      for i in range(k)])


@pytest.mark.parametrize("side", [proj.RIGHT, proj.LEFT])
@pytest.mark.parametrize("protocol", ["avg", "avg_svd", "ajive"])
def test_hetero_factored_matches_dense_lift(side, protocol):
    """sync_block_hetero_factored ≡ the dense per-client lift
    (``reference.dense_sync_block``: lift each client with its own basis,
    sync, re-project onto the client-0 basis) to ≤1e-5 for every protocol
    and both sides — the r×r transfer-Gram path replaces the last dense
    (C, m, n) 𝒮."""
    r, dim, k = 4, 24, 5
    v_stack = _structured_stack(jax.random.PRNGKey(3), side, k=k, r=r)
    b_stack = _hetero_bases(jax.random.PRNGKey(7), k, dim, r)
    w = jnp.array([1, 2, 1, 1, 3.0])
    dense = reference.dense_sync_block(protocol, v_stack, b_stack, w, r, side)
    fact = sync.sync_block_hetero_factored(protocol, v_stack, b_stack, side,
                                           weights=w, rank=r)
    assert fact.shape == dense.shape == v_stack.shape[1:]
    assert jnp.allclose(fact, dense, atol=1e-5), float(
        jnp.max(jnp.abs(fact - dense)))


@pytest.mark.parametrize("protocol", ["avg", "avg_svd", "ajive"])
def test_hetero_factored_shared_bases_degenerates(protocol):
    """With every client on the same basis the hetero path must agree with
    the shared-basis factored sync (the transfer Grams become identity)."""
    r, dim, k = 4, 24, 5
    v_stack = _structured_stack(jax.random.PRNGKey(4), proj.RIGHT, k=k, r=r)
    basis = proj.random_basis(0, dim, r)
    b_stack = jnp.broadcast_to(basis, (k,) + basis.shape)
    shared = sync.sync_block_synced_factored(protocol, v_stack, proj.RIGHT,
                                             rank=r)
    het = sync.sync_block_hetero_factored(protocol, v_stack, b_stack,
                                          proj.RIGHT, rank=r)
    assert jnp.allclose(het, shared, atol=1e-5)


def test_hetero_factored_stacked_blocks():
    """Stacked scan blocks (C, nb, ·, r) vmap over the layer dim."""
    r, dim, k, nb = 4, 24, 5, 2
    v4 = jnp.stack([_structured_stack(jax.random.PRNGKey(i), proj.RIGHT,
                                      k=k, r=r) for i in range(nb)], axis=1)
    b4 = jnp.stack([_hetero_bases(jax.random.PRNGKey(10 + i), k, dim, r)
                    for i in range(nb)], axis=1)
    out = sync.sync_block_hetero_factored("ajive", v4, b4, proj.RIGHT, rank=r)
    assert out.shape == v4.shape[1:]
    for i in range(nb):
        single = sync.sync_block_hetero_factored("ajive", v4[:, i], b4[:, i],
                                                 proj.RIGHT, rank=r)
        assert jnp.allclose(out[i], single, atol=1e-6)


def test_hetero_factored_none():
    v_stack = _structured_stack(jax.random.PRNGKey(5), proj.RIGHT)
    b_stack = _hetero_bases(jax.random.PRNGKey(6), 5, 24, 4)
    assert sync.sync_block_hetero_factored("none", v_stack, b_stack,
                                           proj.RIGHT) is None

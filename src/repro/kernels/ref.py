"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each reference is the mathematically-plain implementation with fp32
accumulation — the kernels must match these on CPU (interpret=True) across
the shape/dtype sweeps in tests/test_kernels_*.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def galore_adamw_ref(w, g, basis, m, v, *, count, b1=0.9, b2=0.999, eps=1e-8,
                     lr=1e-3, weight_decay=0.0):
    """Fused right-projection GaLoreAdamW step for one block.

    w (M, N) params; g (M, N) dense gradient; basis (N, r); m, v (M, r)
    projected fp32 moments; count = post-increment step (for bias correction).
    Returns (new_w, new_m, new_v).
    """
    g32 = g.astype(jnp.float32)
    gt = g32 @ basis.astype(jnp.float32)                  # (M, r)
    m_new = b1 * m + (1 - b1) * gt
    v_new = b2 * v + (1 - b2) * gt * gt
    c = jnp.asarray(count, jnp.float32)
    c1 = 1 - b1 ** c
    c2 = 1 - b2 ** c
    ut = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)      # (M, r)
    u = ut @ basis.astype(jnp.float32).T                  # (M, N)
    w32 = w.astype(jnp.float32)
    w_new = w32 - lr * u - lr * weight_decay * w32
    return w_new.astype(w.dtype), m_new, v_new


def galore_precond_ref(g, basis, m, v, *, count, side, b1=0.9, b2=0.999,
                       eps=1e-8, project_back=True):
    """Preconditioning-only GaLore step for one block, either side.

    right: basis (N, r), m, v (M, r); left: basis (M, r), m, v (r, N).
    Returns (u, new_m, new_v): u is the ambient (M, N) direction, or the
    projected ũ in the moment shape when ``project_back=False``.
    """
    g32 = g.astype(jnp.float32)
    b32 = basis.astype(jnp.float32)
    gt = g32 @ b32 if side == "right" else b32.T @ g32
    m_new = b1 * m + (1 - b1) * gt
    v_new = b2 * v + (1 - b2) * gt * gt
    c = jnp.asarray(count, jnp.float32)
    ut = (m_new / (1 - b1 ** c)) / (jnp.sqrt(v_new / (1 - b2 ** c)) + eps)
    if not project_back:
        return ut, m_new, v_new
    u = ut @ b32.T if side == "right" else b32 @ ut
    return u, m_new, v_new


def lowrank_linear_ref(x, w, basis, rt, scale, *, side):
    """Lift-free low-rank linear apply for one factored block.

    x (..., t, m); w (m, n); right: basis (n, r), rt (m, r) —
    ``y = scale·(x@w) + (x@rt)@basisᵀ``; left: basis (m, r), rt (r, n) —
    ``y = scale·(x@w) + (x@basis)@rt``. fp32 accumulation; result in the
    base-GEMM dtype. Mathematically ``x @ (scale·w + lift(rt, basis))``
    with the dense lifted weight never materialized.
    """
    x32 = x.astype(jnp.float32)
    base = scale * (x32 @ w.astype(jnp.float32))
    b32 = basis.astype(jnp.float32)
    r32 = rt.astype(jnp.float32)
    if side == "right":
        delta = (x32 @ r32) @ b32.T
    else:
        delta = (x32 @ b32) @ r32
    return (base + delta).astype(jnp.result_type(x.dtype, w.dtype))


def lowrank_linear_batched_ref(x, w, bases, rts, scales, ids, *, side):
    """Per-row heterogeneous-adapter apply (the serving batch shape).

    x (B, t, m) or (B, m); w (m, n) shared base; bases/rts/scales are
    (G, ·, ·)/(G,) adapter tables; ids (B,) selects each row's adapter:
    ``y[b] = scales[ids[b]]·(x[b]@w) + split-matmul(x[b], bases[ids[b]],
    rts[ids[b]])``. Plain gather + einsum with fp32 accumulation — the
    allclose target for the scalar-prefetch Pallas kernel.
    """
    squeeze_t = x.ndim == 2
    x3 = (x[:, None, :] if squeeze_t else x).astype(jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)
    s = jnp.asarray(scales, jnp.float32)[ids][:, None, None]
    base = s * (x3 @ w.astype(jnp.float32))
    bg = bases.astype(jnp.float32)[ids]
    rg = rts.astype(jnp.float32)[ids]
    if side == "right":
        delta = jnp.einsum("btr,bnr->btn", jnp.einsum("btm,bmr->btr", x3, rg),
                           bg)
    else:
        delta = jnp.einsum("btr,brn->btn", jnp.einsum("btm,bmr->btr", x3, bg),
                           rg)
    y = (base + delta).astype(jnp.result_type(x.dtype, w.dtype))
    return y[:, 0, :] if squeeze_t else y


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q (B, Lq, H, D), k/v (B, Lk, Hkv, D), GQA by head grouping."""
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    groups = h // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    qg = q.reshape(b, lq, hkv, groups, d).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(lq)[:, None] + (lk - lq)
        kpos = jnp.arange(lk)[None, :]
        mask = kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(b, lq, h, d).astype(q.dtype)


def rwkv6_scan_ref(r, k, v, w, u, s0=None):
    """RWKV6 WKV recurrence. r,k,v,w (B, L, H, D); u (H, D); s0 (B, H, D, D).

        y_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ);  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

    Returns (y (B, L, H, D), s_final).
    """
    b, l, h, d = r.shape
    s = (jnp.zeros((b, h, d, d), jnp.float32) if s0 is None
         else s0.astype(jnp.float32))

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp
        kv = jnp.einsum("bhk,bhv->bhkv", k_t.astype(jnp.float32),
                        v_t.astype(jnp.float32))
        y = jnp.einsum("bhk,bhkv->bhv", r_t.astype(jnp.float32),
                       s + u[None, :, :, None] * kv)
        s = w_t.astype(jnp.float32)[..., None] * s + kv
        return s, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    s, ys = jax.lax.scan(step, s, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), s

"""Flash attention as Pallas TPU kernels, forward and backward (custom VJP),
with GQA, causal and sliding-window masks.

q (B, Lq, H, D) and k/v (B, Lk, Hkv, D) are read in place as (B, L, H·D):
a grid step takes a *head block* of KV heads (and the ``H/Hkv`` query heads
of each) whose lanes make a multiple of 128 (``head_block``), and splits the
heads inside the kernel. No (B, H, L, D) transpose is written to HBM, and
no (Lq, Lk) object ever exists there: score tiles live in VMEM.

Every kernel forms its score tiles transposed, ``sᵀ = k qᵀ`` with keys on
sublanes and queries on lanes, so per-query statistics are lane-dense rows
and the reductions over keys run across sublanes. Forward, grid (B, head
blocks, Lq/block_q): K and V of the head block stay in VMEM and are
streamed through in block_k tiles with the online softmax

    m_new = max(m, colmax(sᵀ));  pᵀ = exp(sᵀ - m_new)
    l     = e^{m-m_new} l + colsum(pᵀ);  accᵀ = e^{m-m_new} accᵀ + vᵀ pᵀ

and each query row's logsumexp ``m + log l`` is written beside the output.
Backward: two kernels recompute ``pᵀ = exp(sᵀ - lse)`` per tile. dQ (grid
over query tiles) also forms each row's ``di = rowsum(o·do)`` and writes it
out for dK/dV (grid over key tiles, streaming the query tiles).

Operands stay in their dtype: bf16 q/k/v go to the MXU as bf16 with fp32
accumulation, the softmax statistics are fp32, and the probabilities (and
their gradients) are cast to the operand dtype for the second product, as
``models.attention.attend`` does. Under causality the tiles wholly in the
future (or, with a window, wholly before it) are skipped, and only tiles
that straddle the diagonal or the window edge are masked. Queries are
suffix-aligned: query row i sits at absolute position ``Lk - Lq + i``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
STEP_ELEMS = 1 << 17
VMEM_DEFAULT = 16 << 20    # the compiler's scoped VMEM without a limit
VMEM_MAX = 100 << 20       # of the v5e's 128 MiB


class _Cfg(NamedTuple):
    causal: bool
    window: int
    scale: float
    block_q: int
    block_k: int
    block_h: int
    interpret: bool


def head_block(hkv: int, d: int, group: int, rows: int) -> int:
    """KV heads per grid step: the most whose query block (``rows`` by the
    lanes of their ``group`` query heads each) holds at most STEP_ELEMS
    elements, among the counts whose lanes are a multiple of 128 (or all
    heads); else the fewest such. Wider steps mean fewer grid steps, the
    bound keeps a step's unrolled heads within VMEM."""
    valid = [kb for kb in range(1, hkv + 1)
             if hkv % kb == 0 and ((kb * d) % LANES == 0 or kb == hkv)]
    fit = [kb for kb in valid if rows * kb * group * d <= STEP_ELEMS]
    return max(fit) if fit else valid[0]


def _ceil_div(x, n):
    """ceil(max(x, 0) / n) for a traced int ``x``."""
    return (jnp.maximum(x, 0) + n - 1) // n


def _kv_tiles(qa, bq, bk, nk, causal, window):
    """Key tiles for the query rows [qa, qa+bq): [lo, hi) hold a visible key,
    and of them [a, b) need no mask."""
    lo = _ceil_div(qa - window + 2 - bk, bk) if window else 0
    hi = jnp.minimum(nk, (qa + bq - 1) // bk + 1) if causal else nk
    clean_lo = _ceil_div(qa + bq - window, bk) if window else 0
    clean_hi = (qa + 1) // bk if causal else nk
    a = jnp.clip(clean_lo, lo, hi)
    return lo, a, jnp.clip(clean_hi, a, hi), hi


def _q_tiles(ka, bk, bq, nq, offset, causal, window):
    """Query tiles (query s at rows [offset + s·bq, +bq)) for the keys
    [ka, ka+bk): [lo, hi) see a key, and of them [a, b) need no mask."""
    lo = _ceil_div(ka - offset - bq + 1, bq) if causal else 0
    hi = (jnp.minimum(nq, jnp.maximum(window + ka + bk - 2 - offset + bq, 0)
                      // bq) if window else nq)
    clean_lo = _ceil_div(ka + bk - 1 - offset, bq) if causal else 0
    clean_hi = (jnp.maximum(window + ka - offset, 0) // bq if window
                else nq)
    a = jnp.clip(clean_lo, lo, hi)
    return lo, a, jnp.clip(clean_hi, a, hi), hi


def _visible(qpos, kpos, causal, window):
    ok = kpos <= qpos if causal else jnp.ones(qpos.shape, jnp.bool_)
    if window:
        ok &= (qpos - kpos) < window
    return ok


def _dot_t(a, b):
    """a @ bᵀ with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _row(col):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _three_loops(bounds, step, carry):
    """Run ``step(t, carry, masked)`` over the masked tiles [lo, a), the
    clean tiles [a, b) and the masked tiles [b, hi)."""
    lo, a, b, hi = bounds
    carry = jax.lax.fori_loop(lo, a, functools.partial(step, masked=True),
                              carry)
    carry = jax.lax.fori_loop(a, b, functools.partial(step, masked=False),
                              carry)
    return jax.lax.fori_loop(b, hi, functools.partial(step, masked=True),
                             carry)


def _tn(a, b):
    """aᵀ @ b with fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, cfg, d, group,
                offset):
    bq, bk = q_ref.shape[0], cfg.block_k
    heads = range(q_ref.shape[1] // d)
    qa = offset + pl.program_id(2) * bq
    bounds = _kv_tiles(qa, bq, bk, k_ref.shape[0] // bk, cfg.causal,
                       cfg.window)
    qpos = qa + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)

    def step(t, carry, masked):
        rows = pl.ds(pl.multiple_of(t * bk, bk), bk)
        keep = (_visible(qpos, t * bk + kidx, cfg.causal, cfg.window)
                if masked else None)
        out = []
        for h, (acc, m, l) in zip(heads, carry):
            kcols = pl.ds((h // group) * d, d)
            v = v_ref[rows, kcols]
            s = _dot_t(k_ref[rows, kcols],
                       q_ref[:, pl.ds(h * d, d)]) * cfg.scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
            out.append((alpha * acc + _tn(v, p.astype(v.dtype)), m_new, l))
        return tuple(out)

    carry = _three_loops(bounds, step, tuple(
        (jnp.zeros((d, bq), jnp.float32),
         jnp.full((1, bq), NEG_INF, jnp.float32),
         jnp.zeros((1, bq), jnp.float32)) for _ in heads))
    for h, (acc, m, l) in zip(heads, carry):
        o_ref[:, pl.ds(h * d, d)] = (acc / l).T.astype(o_ref.dtype)
        lse_ref[pl.ds(h, 1), :] = m + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, di_ref, *,
               cfg, d, group, offset):
    bq, bk = q_ref.shape[0], cfg.block_k
    heads = range(q_ref.shape[1] // d)
    qa = offset + pl.program_id(2) * bq
    bounds = _kv_tiles(qa, bq, bk, k_ref.shape[0] // bk, cfg.causal,
                       cfg.window)
    qpos = qa + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    for h in heads:
        cols = pl.ds(h * d, d)
        di_ref[pl.ds(h, 1), :] = _row(jnp.sum(
            o_ref[:, cols].astype(jnp.float32)
            * do_ref[:, cols].astype(jnp.float32), axis=1, keepdims=True))

    def step(t, carry, masked):
        rows = pl.ds(pl.multiple_of(t * bk, bk), bk)
        keep = (_visible(qpos, t * bk + kidx, cfg.causal, cfg.window)
                if masked else None)
        out = []
        for h, dq in zip(heads, carry):
            cols, kcols = pl.ds(h * d, d), pl.ds((h // group) * d, d)
            k = k_ref[rows, kcols]
            p = jnp.exp(_dot_t(k, q_ref[:, cols]) * cfg.scale
                        - lse_ref[pl.ds(h, 1), :])
            if masked:
                p = jnp.where(keep, p, 0.0)
            ds = p * (_dot_t(v_ref[rows, kcols], do_ref[:, cols])
                      - di_ref[pl.ds(h, 1), :])
            out.append(dq + _tn(k, ds.astype(k.dtype)))
        return tuple(out)

    carry = _three_loops(bounds, step, tuple(
        jnp.zeros((d, bq), jnp.float32) for _ in heads))
    for h, dq in zip(heads, carry):
        dq_ref[:, pl.ds(h * d, d)] = (dq * cfg.scale).T.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                *, cfg, d, group, offset):
    bk, bq = k_ref.shape[0], cfg.block_q
    kv_heads = range(k_ref.shape[1] // d)
    ka = pl.program_id(2) * bk
    bounds = _q_tiles(ka, bk, bq, q_ref.shape[0] // bq, offset, cfg.causal,
                      cfg.window)
    kpos = ka + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qidx = offset + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    ks = [k_ref[:, pl.ds(j * d, d)] for j in kv_heads]
    vs = [v_ref[:, pl.ds(j * d, d)] for j in kv_heads]

    def step(s, carry, masked):
        rows = pl.ds(pl.multiple_of(s * bq, bq), bq)
        keep = (_visible(s * bq + qidx, kpos, cfg.causal, cfg.window)
                if masked else None)
        out = []
        for j, (dk, dv) in zip(kv_heads, carry):
            for h in range(j * group, (j + 1) * group):
                cols = pl.ds(h * d, d)
                q, do = q_ref[rows, cols], do_ref[rows, cols]
                pt = jnp.exp(_dot_t(ks[j], q) * cfg.scale
                             - lse_ref[pl.ds(h, 1), rows])
                if masked:
                    pt = jnp.where(keep, pt, 0.0)
                dv = dv + _dot(pt.astype(do.dtype), do)
                dst = pt * (_dot_t(vs[j], do) - di_ref[pl.ds(h, 1), rows])
                dk = dk + _dot(dst.astype(q.dtype), q)
            out.append((dk, dv))
        return tuple(out)

    carry = _three_loops(bounds, step, tuple(
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32))
        for _ in kv_heads))
    for j, (dk, dv) in zip(kv_heads, carry):
        dk_ref[:, pl.ds(j * d, d)] = (dk * cfg.scale).astype(dk_ref.dtype)
        dv_ref[:, pl.ds(j * d, d)] = dv.astype(dv_ref.dtype)


def _layout(q, k, cfg):
    """(kernel constants, head blocks, query heads per block, lanes of a
    query block, lanes of a key block)."""
    lq, h, d = q.shape[1:]
    lk, hkv = k.shape[1], k.shape[2]
    group, kb = h // hkv, cfg.block_h
    consts = dict(d=d, group=group, offset=lk - lq)
    return consts, hkv // kb, kb * group, kb * group * d, kb * d


def _params(*arrays):
    """A scoped-VMEM limit for a call whose resident blocks (the arrays'
    ``(shape, dtype)``, double-buffered) pass a quarter of the default:
    long sequences keep whole-length K/V or Q/dO blocks, and their score
    tiles need room beside them. None (the default) below that."""
    need = 2 * sum(math.prod(shape) * jnp.dtype(dt).itemsize
                   for shape, dt in arrays)
    if 4 * need <= VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(VMEM_MAX, VMEM_DEFAULT + 4 * need))


def _name(kind, cfg):
    return f"flash_attention_{kind}" + ("_causal" if cfg.causal else "")


def _forward(q, k, v, cfg: _Cfg):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    consts, nhb, hq, wq, wk = _layout(q, k, cfg)
    bq = cfg.block_q
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, **consts),
        grid=(b, nhb, lq // bq),
        in_specs=[pl.BlockSpec((None, bq, wq), lambda i, g, t: (i, t, g)),
                  pl.BlockSpec((None, lk, wk), lambda i, g, t: (i, 0, g)),
                  pl.BlockSpec((None, lk, wk), lambda i, g, t: (i, 0, g))],
        out_specs=[pl.BlockSpec((None, bq, wq), lambda i, g, t: (i, t, g)),
                   pl.BlockSpec((None, None, hq, bq),
                                lambda i, g, t: (i, g, 0, t))],
        out_shape=[jax.ShapeDtypeStruct((b, lq, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, nhb, hq, lq), jnp.float32)],
        compiler_params=_params(((bq, wq), q.dtype), ((bq, wq), q.dtype),
                                ((lk, wk), k.dtype), ((lk, wk), k.dtype),
                                ((hq, bq), jnp.float32)),
        interpret=cfg.interpret,
        name=_name("fwd", cfg),
    )(q.reshape(b, lq, h * d), k.reshape(b, lk, -1), v.reshape(b, lk, -1))
    return o.reshape(q.shape), lse


def _backward(cfg: _Cfg, res, do):
    q, k, v, o, lse = res
    b, lq, h, d = q.shape
    lk = k.shape[1]
    consts, nhb, hq, wq, wk = _layout(q, k, cfg)
    bq, bk = cfg.block_q, cfg.block_k
    q2, k2, v2, o2, do2 = (q.reshape(b, lq, h * d), k.reshape(b, lk, -1),
                           v.reshape(b, lk, -1), o.reshape(b, lq, h * d),
                           do.reshape(b, lq, h * d))
    q_tile = pl.BlockSpec((None, bq, wq), lambda i, g, t: (i, t, g))
    q_all = pl.BlockSpec((None, lq, wq), lambda i, g, t: (i, 0, g))
    kv_tile = pl.BlockSpec((None, bk, wk), lambda i, g, t: (i, t, g))
    kv_all = pl.BlockSpec((None, lk, wk), lambda i, g, t: (i, 0, g))
    stat_tile = pl.BlockSpec((None, None, hq, bq),
                             lambda i, g, t: (i, g, 0, t))
    stat_all = pl.BlockSpec((None, None, hq, lq),
                            lambda i, g, t: (i, g, 0, 0))
    dq, di = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, **consts),
        grid=(b, nhb, lq // bq),
        in_specs=[q_tile, kv_all, kv_all, q_tile, q_tile, stat_tile],
        out_specs=[q_tile, stat_tile],
        out_shape=[jax.ShapeDtypeStruct(q2.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        compiler_params=_params(*[((bq, wq), q.dtype)] * 4,
                                ((lk, wk), k.dtype), ((lk, wk), k.dtype),
                                ((hq, bq), jnp.float32),
                                ((hq, bq), jnp.float32)),
        interpret=cfg.interpret,
        name=_name("dq", cfg),
    )(q2, k2, v2, o2, do2, lse)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, **consts),
        grid=(b, nhb, lk // bk),
        in_specs=[q_all, kv_tile, kv_tile, q_all, stat_all, stat_all],
        out_specs=[kv_tile, kv_tile],
        out_shape=[jax.ShapeDtypeStruct(k2.shape, k.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        compiler_params=_params(((lq, wq), q.dtype), ((lq, wq), q.dtype),
                                *[((bk, wk), k.dtype)] * 4,
                                ((hq, lq), jnp.float32),
                                ((hq, lq), jnp.float32)),
        interpret=cfg.interpret,
        name=_name("dkv", cfg),
    )(q2, k2, v2, do2, lse, di)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    return _forward(q, k, v, cfg)[0]


def _flash_fwd(q, k, v, cfg):
    o, lse = _forward(q, k, v, cfg)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _backward)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=512, block_k=256, interpret=False):
    """q (B, Lq, H, D), k/v (B, Lk, Hkv, D) with H % Hkv == 0 and Lk >= Lq.

    Returns (B, Lq, H, D), differentiable in q, k and v. Suffix-aligned
    causal masking: query position i maps to absolute position
    (Lk - Lq) + i. Tiles are ``gcd(block, L)`` rows; on the chip they are
    multiples of 128 (the logsumexp rows are lane vectors). ``head_block``
    sets the KV heads of a grid step."""
    lq, h, d = q.shape[1:]
    lk, hkv = k.shape[1], k.shape[2]
    bq, bk = math.gcd(block_q, lq), math.gcd(block_k, lk)
    if h % hkv or lk < lq:
        raise ValueError(f"flash_attention: q {q.shape}, k {k.shape}")
    if window and not causal:
        raise ValueError("a sliding window is causal: bidirectional "
                         "attention takes window=0")
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    kb = head_block(hkv, d, h // hkv, bq)
    return _flash(q, k, v, _Cfg(causal, window, scale, bq, bk, kb, interpret))

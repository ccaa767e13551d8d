"""Blockwise (flash) attention Pallas TPU kernel with GQA + sliding window.

Grid: (batch, q_heads, Lq/block_q). Per grid step the kernel holds one query
tile (block_q, D) and streams the KV sequence for the matching KV head
(GQA: kv_head = q_head // group) through VMEM in block_k chunks with the
online-softmax recurrence:

    m_new = max(m, rowmax(s));  p = exp(s - m_new)
    l     = e^{m-m_new} l + rowsum(p)
    acc   = e^{m-m_new} acc + p v

Causal and sliding-window masks are applied from absolute positions
(q_offset = Lk - Lq supports decode-style suffix queries). Tiles are
MXU-aligned: block_q/block_k multiples of 128 when the sequence allows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                  block_k, q_offset):
    bq, d = q_ref.shape
    lk = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale        # (bq, D)

    qi = pl.program_id(2)
    q_pos = q_offset + qi * bq + jax.lax.iota(jnp.int32, bq)   # absolute

    n_kv = lk // block_k

    def body(j, carry):
        acc, m_i, l_i = carry
        rows = pl.ds(j * block_k, block_k)
        k = k_ref[rows, :].astype(jnp.float32)        # (bk, D)
        v = v_ref[rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((bq, block_k), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, n_kv, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128, interpret=False):
    """q (B, Lq, H, D), k/v (B, Lk, Hkv, D) with H % Hkv == 0.

    Returns (B, Lq, H, D). Suffix-aligned causal masking: query position i
    maps to absolute position (Lk - Lq) + i.
    """
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    groups = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    assert lq % bq == 0 and lk % bk == 0

    # (B, L, H, D) -> (B, H, L, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, block_k=bk, q_offset=lk - lq)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, lq // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, lk, d),
                         lambda bi, hi, qi, g=groups: (bi, hi // g, 0, 0)),
            pl.BlockSpec((None, None, lk, d),
                         lambda bi, hi, qi, g=groups: (bi, hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

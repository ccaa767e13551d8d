"""Attention: GQA (full / sliding-window causal, or bidirectional for an
encoder) and MLA (DeepSeek-V2).

Decode uses a ring-buffer KV cache (size = window for sliding-window archs,
so long_500k decode keeps O(window) memory). MLA decode uses the *absorbed*
form: scores and context are computed in the compressed kv_lora space, so the
per-token cache is (kv_lora + rope_dim) — the whole point of MLA.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .layers import apply_rope, constrain, dense, dense_init

NEG_INF = -1e30


# ------------------------------------------------------------------- GQA ----

def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
             qkv_bias: bool = False, dtype=jnp.float32, out_bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype=dtype),
         "wk": dense_init(ks[1], (d_model, n_kv * head_dim), dtype=dtype),
         "wv": dense_init(ks[2], (d_model, n_kv * head_dim), dtype=dtype),
         "wo": dense_init(ks[3], (n_heads * head_dim, d_model), dtype=dtype)}
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    if out_bias:
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: int = 0) -> jnp.ndarray:
    """(..., Lq, Lk) boolean mask: attend iff k_pos <= q_pos and, for
    sliding-window attention, q_pos - k_pos < window."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    mask = diff >= 0
    if window:
        mask = mask & (diff < window)
    return mask


@jax.named_scope("model.attention")
def attend(q, k, v, mask) -> jnp.ndarray:
    """q (B,Lq,H,hd), k/v (B,Lk,Hkv,hd) with H % Hkv == 0; mask (B|1,Lq,Lk),
    or None for bidirectional attention (every query sees every key).

    Matmuls take bf16 operands with fp32 accumulation
    (``preferred_element_type``) — no materialized fp32 copy of K/V, which
    matters enormously when K/V is a 32k-slot decode cache (§Perf iteration:
    removing the cache-sized converts cut the decode memory term ~2×).
    Softmax stays fp32; the probabilities are cast back to the value dtype.
    """
    b, lq, h, hd = q.shape
    hkv = k.shape[2]
    groups = h // hkv
    qg = q.reshape(b, lq, hkv, groups, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, lq, h, hd).astype(q.dtype)


@jax.named_scope("model.attention")
def blockwise_attend(q, k, v, *, window=0, chunk_q=2048, chunk_k=2048,
                     q_start=0, causal=True) -> jnp.ndarray:
    """Flash-style blockwise causal attention in pure XLA (§Perf iteration B).

    Both the query and key sequences are chunked; (q-chunk, k-chunk) pairs
    that are *entirely* masked — future blocks under causality, stale blocks
    under a sliding window — are skipped STATICALLY, so the saved FLOPs and
    bytes are real in the compiled HLO (≈2× for causal, window/L for SWA).
    ``causal=False`` (an encoder) keeps every pair and masks none.
    Per-pair online-softmax statistics keep the working set at
    (B, H, chunk_q, chunk_k); the full (L, L) score tensor never exists.
    The Pallas kernel (kernels/flash_attention.py) is the TPU-native twin of
    this computation with explicit VMEM tiling.
    """
    b, lq, h, hd = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    cq, ck = min(chunk_q, lq), min(chunk_k, lk)
    assert lq % cq == 0 and lk % ck == 0
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(b, lq, hkv, g, hd)
    if not causal and window:
        raise ValueError("a sliding window is causal: bidirectional "
                         "attention takes window=0")

    outs = []
    for qi in range(lq // cq):
        q_blk = qg[:, qi * cq:(qi + 1) * cq]
        q_lo = q_start + qi * cq
        q_hi = q_lo + cq - 1
        m_i = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l_i = jnp.zeros((b, hkv, g, cq), jnp.float32)
        acc = jnp.zeros((b, hkv, g, cq, hd), jnp.float32)
        for ki in range(lk // ck):
            k_lo, k_hi = ki * ck, ki * ck + ck - 1
            if causal and k_lo > q_hi:
                continue                      # fully in the future
            if window and k_hi < q_lo - window + 1:
                continue                      # fully outside the window
            k_blk = k[:, k_lo:k_lo + ck]
            v_blk = v[:, k_lo:k_lo + ck]
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            crosses_causal = causal and k_hi > q_lo
            crosses_window = window and k_lo < q_hi - window + 1
            if crosses_causal or crosses_window:
                qp = q_lo + jnp.arange(cq)
                kp = k_lo + jnp.arange(ck)
                mask = causal_mask(qp, kp, window)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_i - m_new)
            p_ = jnp.exp(s - m_new[..., None])
            l_i = alpha * l_i + jnp.sum(p_, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p_.astype(v.dtype), v_blk,
                preferred_element_type=jnp.float32)
            m_i = m_new
        out = acc / jnp.maximum(l_i, 1e-30)[..., None]
        outs.append(out)
    full = jnp.concatenate(outs, axis=3)      # (b, hkv, g, lq, hd)
    return full.transpose(0, 3, 1, 2, 4).reshape(b, lq, h, hd).astype(q.dtype)


def flash_route(lq: int, lk: int, window: int, attn_chunk: int) -> bool:
    """Whether full-sequence attention over ``lq`` queries and ``lk`` keys
    takes the Pallas flash kernel (``kernels.flash_attention``) instead of
    :func:`attend`: the kernels are on (``kernels.ops.use_kernels``: a TPU,
    one device), the sequence is square and a whole number of 128-row
    tiles, shorter than ``attn_chunk`` (from there ``blockwise_attend``
    takes it), and the mask is the plain causal one or none (no window)."""
    return (lq == lk and lq % 128 == 0 and not window
            and not (attn_chunk and lq >= attn_chunk)
            and kops.use_kernels())


@jax.named_scope("model.attention")
def flash_attend(q, k, v, *, causal=True) -> jnp.ndarray:
    """:func:`attend` as the fused flash kernel: the same shapes, masks and
    precision, forward and custom-VJP backward, with no (L, L) tensor in
    HBM. The scope holds the kernel's backward too."""
    return kops.flash_attention(q, k, v, causal=causal)


def gqa_forward(p, x, positions, *, n_heads, n_kv, head_dim, rope=True,
                rope_theta=1e4, window=0, attn_chunk=0, causal=True):
    """Training/prefill attention over a full sequence. x (B,L,D).
    ``causal=False`` is an encoder's: no mask is built and no block is
    skipped. ``positions`` are consecutive (every caller passes
    ``arange(L)``), so the causal mask is the index order the flash kernel
    applies (:func:`flash_route`)."""
    b, l, _ = x.shape
    q = dense(x, p["wq"]) + p.get("bq", 0)
    k = dense(x, p["wk"]) + p.get("bk", 0)
    v = dense(x, p["wv"]) + p.get("bv", 0)
    q = constrain(_split_heads(q, n_heads, head_dim),
                  "batch", None, "model", None)
    k = constrain(_split_heads(k, n_kv, head_dim),
                  "batch", None, "model", None)
    v = constrain(_split_heads(v, n_kv, head_dim),
                  "batch", None, "model", None)
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if attn_chunk and l >= attn_chunk:
        c = min(attn_chunk, l // 2)
        ctx = blockwise_attend(q, k, v, window=window, chunk_q=c, chunk_k=c,
                               causal=causal)
    elif flash_route(l, l, window, attn_chunk):
        ctx = flash_attend(q, k, v, causal=causal)
    elif causal:
        mask = causal_mask(positions, positions, window)
        if mask.ndim == 2:
            mask = mask[None]
        ctx = attend(q, k, v, mask)
    else:
        ctx = attend(q, k, v, None)
    ctx = constrain(ctx, "batch", None, "model", None)
    out = dense(ctx.reshape(b, l, n_heads * head_dim), p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out, (k, v)


class KVCache(NamedTuple):
    k: jnp.ndarray      # (B, S, Hkv, hd)
    v: jnp.ndarray      # (B, S, Hkv, hd)
    pos: jnp.ndarray    # (B, S) absolute position of each slot, -1 = empty


def kv_cache_init(batch: int, size: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(k=jnp.zeros((batch, size, n_kv, head_dim), dtype),
                   v=jnp.zeros((batch, size, n_kv, head_dim), dtype),
                   pos=jnp.full((batch, size), -1, jnp.int32))


def kv_cache_write(cache: KVCache, k_new, v_new, t0) -> KVCache:
    """Ring-buffer write of (B, Ln, Hkv, hd) starting at absolute pos t0.

    ``t0`` scalar: every row writes the same slots (the homogeneous decode
    batch — unchanged fast path). ``t0`` (B,): per-row start positions, the
    continuous-batching layout where each slot sits at its own depth."""
    b, ln = k_new.shape[:2]
    size = cache.k.shape[1]
    if jnp.ndim(t0):
        pos = t0[:, None] + jnp.arange(ln)[None, :]          # (B, Ln)
        slots = pos % size
        rows = jnp.arange(b)[:, None]
        k = cache.k.at[rows, slots].set(k_new.astype(cache.k.dtype))
        v = cache.v.at[rows, slots].set(v_new.astype(cache.v.dtype))
        p = cache.pos.at[rows, slots].set(pos.astype(jnp.int32))
        return KVCache(k=k, v=v, pos=p)
    pos = t0 + jnp.arange(ln)
    slots = pos % size
    k = cache.k.at[:, slots].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[:, slots].set(v_new.astype(cache.v.dtype))
    p = cache.pos.at[:, slots].set(jnp.broadcast_to(pos, (b, ln)).astype(jnp.int32))
    return KVCache(k=k, v=v, pos=p)


def gqa_decode(p, x, cache: KVCache, t, *, n_heads, n_kv, head_dim,
               rope=True, rope_theta=1e4, window=0):
    """One-token decode. x (B,1,D); t scalar absolute position, or (B,)
    per-row positions (continuous-batching slots at different depths)."""
    b = x.shape[0]
    q = x @ p["wq"] + p.get("bq", 0)
    k = x @ p["wk"] + p.get("bk", 0)
    v = x @ p["wv"] + p.get("bv", 0)
    q = _split_heads(q, n_heads, head_dim)
    k = _split_heads(k, n_kv, head_dim)
    v = _split_heads(v, n_kv, head_dim)
    pos1 = (t[:, None].astype(jnp.int32) if jnp.ndim(t)
            else jnp.full((1,), t, jnp.int32))
    if rope:
        q = apply_rope(q, pos1, rope_theta)
        k = apply_rope(k, pos1, rope_theta)
    cache = kv_cache_write(cache, k, v, t)
    q_pos = jnp.broadcast_to(pos1, (b, 1))
    mask = causal_mask(q_pos, cache.pos, window) & (cache.pos[:, None, :] >= 0)
    ctx = attend(q, cache.k, cache.v, mask)
    return ctx.reshape(b, 1, n_heads * head_dim) @ p["wo"], cache


# ------------------------------------------------------------------- MLA ----

def mla_init(key, d_model: int, n_heads: int, *, q_lora: int, kv_lora: int,
             qk_nope: int, qk_rope: int, v_dim: int, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    return {
        "q_a": dense_init(ks[0], (d_model, q_lora), dtype=dtype),
        "q_a_norm": jnp.ones((q_lora,), dtype),
        "q_b": dense_init(ks[1], (q_lora, n_heads * (qk_nope + qk_rope)), dtype=dtype),
        "kv_a": dense_init(ks[2], (d_model, kv_lora + qk_rope), dtype=dtype),
        "kv_a_norm": jnp.ones((kv_lora,), dtype),
        "kv_b": dense_init(ks[3], (kv_lora, n_heads * (qk_nope + v_dim)), dtype=dtype),
        "wo": dense_init(ks[4], (n_heads * v_dim, d_model), dtype=dtype),
    }


def _mla_qkv(p, x, positions, n_heads, qk_nope, qk_rope, kv_lora, rope_theta):
    from .layers import rms_norm
    b, l, _ = x.shape
    q = dense(rms_norm(dense(x, p["q_a"]), p["q_a_norm"]), p["q_b"])
    q = constrain(q.reshape(b, l, n_heads, qk_nope + qk_rope),
                  "batch", None, "model", None)
    q_nope, q_pe = q[..., :qk_nope], q[..., qk_nope:]
    q_pe = apply_rope(q_pe, positions, rope_theta)

    kv = dense(x, p["kv_a"])
    c_kv = constrain(rms_norm(kv[..., :kv_lora], p["kv_a_norm"]),
                     "batch", None, None)                  # (B,L,kv_lora)
    k_pe = kv[..., kv_lora:][:, :, None, :]                 # (B,L,1,rope)
    k_pe = apply_rope(k_pe, positions, rope_theta)[:, :, 0]  # (B,L,rope)
    return q_nope, q_pe, c_kv, k_pe


def mla_forward(p, x, positions, *, n_heads, qk_nope, qk_rope, kv_lora,
                v_dim, rope_theta=1e4, window=0, attn_chunk=0):
    """Training/prefill MLA with expanded K/V (compute-friendly at long Lq).

    With ``attn_chunk`` the KV expansion happens PER CHUNK inside the
    blockwise loop — the full (B, L, H, d) expanded K/V tensors (128 heads!)
    are never materialized, and causally-dead blocks are skipped statically.
    """
    b, l, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(p, x, positions, n_heads, qk_nope,
                                        qk_rope, kv_lora, rope_theta)
    scale = 1.0 / jnp.sqrt(qk_nope + qk_rope).astype(jnp.float32)

    if attn_chunk and l >= attn_chunk:
        ctx = _mla_blockwise(q_nope, q_pe, c_kv, k_pe, p["kv_b"], qk_nope,
                             scale, window, min(attn_chunk, l // 2))
    else:
        # Expand on the activation side (kv_b consumed as one delta-aware
        # matmul, then reshape/split the result — identical per-element dots
        # to the weight-side reshape + einsum it replaces).
        kv_full = dense(c_kv, p["kv_b"]).reshape(b, l, n_heads,
                                                 qk_nope + v_dim)
        k_nope = kv_full[..., :qk_nope]
        v = kv_full[..., qk_nope:]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32)) * scale
        mask = causal_mask(positions, positions, window)
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out = dense(ctx.reshape(b, l, n_heads * v_dim), p["wo"])
    return out, (c_kv, k_pe)


def _mla_blockwise(q_nope, q_pe, c_kv, k_pe, kv_b, qk_nope, scale, window,
                   chunk):
    b, lq, h, _ = q_nope.shape
    v_dim = kv_b.shape[-1] // h - qk_nope
    cq = ck = min(chunk, lq)
    outs = []
    for qi in range(lq // cq):
        qn_blk = q_nope[:, qi * cq:(qi + 1) * cq]
        qp_blk = q_pe[:, qi * cq:(qi + 1) * cq]
        q_lo, q_hi = qi * cq, qi * cq + cq - 1
        m_i = jnp.full((b, h, cq), NEG_INF, jnp.float32)
        l_i = jnp.zeros((b, h, cq), jnp.float32)
        acc = jnp.zeros((b, h, cq, v_dim), jnp.float32)
        for ki in range(lq // ck):
            k_lo, k_hi = ki * ck, ki * ck + ck - 1
            if k_lo > q_hi:
                continue                       # fully in the future
            if window and k_hi < q_lo - window + 1:
                continue                       # fully outside the window
            ckv_blk = c_kv[:, k_lo:k_lo + ck]
            kpe_blk = k_pe[:, k_lo:k_lo + ck]
            # Per-chunk activation-side expansion (kv_b may be a lift-free
            # LowRankDelta; note the per-chunk reads make the clip-norm
            # probe a per-use sum — see models.layers).
            kv_blk = dense(ckv_blk, kv_b).reshape(b, ck, h, qk_nope + v_dim)
            k_nope_blk = kv_blk[..., :qk_nope]
            v_blk = kv_blk[..., qk_nope:]
            s = (jnp.einsum("bqhd,bshd->bhqs", qn_blk, k_nope_blk,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bsd->bhqs", qp_blk, kpe_blk,
                              preferred_element_type=jnp.float32)) * scale
            if k_hi > q_lo or (window and k_lo < q_hi - window + 1):
                mask = causal_mask(q_lo + jnp.arange(cq),
                                   k_lo + jnp.arange(ck), window)
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_i - m_new)
            p_ = jnp.exp(s - m_new[..., None])
            l_i = alpha * l_i + jnp.sum(p_, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p_.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            m_i = m_new
        outs.append(acc / jnp.maximum(l_i, 1e-30)[..., None])
    full = jnp.concatenate(outs, axis=2)          # (b, h, lq, v_dim)
    return full.transpose(0, 2, 1, 3).astype(q_nope.dtype)


class MLACache(NamedTuple):
    ckv: jnp.ndarray    # (B, S, kv_lora)
    kpe: jnp.ndarray    # (B, S, rope_dim)
    pos: jnp.ndarray    # (B, S)


def mla_cache_init(batch: int, size: int, kv_lora: int, rope_dim: int,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(ckv=jnp.zeros((batch, size, kv_lora), dtype),
                    kpe=jnp.zeros((batch, size, rope_dim), dtype),
                    pos=jnp.full((batch, size), -1, jnp.int32))


def mla_cache_write(cache: MLACache, c_kv, k_pe, t0) -> MLACache:
    b, ln = c_kv.shape[:2]
    size = cache.ckv.shape[1]
    if jnp.ndim(t0):
        pos = t0[:, None] + jnp.arange(ln)[None, :]          # (B, Ln)
        slots = pos % size
        rows = jnp.arange(b)[:, None]
        return MLACache(
            ckv=cache.ckv.at[rows, slots].set(c_kv.astype(cache.ckv.dtype)),
            kpe=cache.kpe.at[rows, slots].set(k_pe.astype(cache.kpe.dtype)),
            pos=cache.pos.at[rows, slots].set(pos.astype(jnp.int32)))
    pos = t0 + jnp.arange(ln)
    slots = pos % size
    return MLACache(
        ckv=cache.ckv.at[:, slots].set(c_kv.astype(cache.ckv.dtype)),
        kpe=cache.kpe.at[:, slots].set(k_pe.astype(cache.kpe.dtype)),
        pos=cache.pos.at[:, slots].set(
            jnp.broadcast_to(pos, (b, ln)).astype(jnp.int32)))


def mla_decode(p, x, cache: MLACache, t, *, n_heads, qk_nope, qk_rope,
               kv_lora, v_dim, rope_theta=1e4, window=0):
    """Absorbed-form single-token MLA decode: attention runs entirely in the
    compressed space — per-step FLOPs O(H·S·(kv_lora + rope)) and the cache
    stores only (kv_lora + rope) per position. ``t`` scalar, or (B,)
    per-row positions for continuous-batching slots."""
    b = x.shape[0]
    pos1 = (t[:, None].astype(jnp.int32) if jnp.ndim(t)
            else jnp.full((1,), t, jnp.int32))
    q_nope, q_pe, c_kv_new, k_pe_new = _mla_qkv(
        p, x, pos1, n_heads, qk_nope, qk_rope, kv_lora, rope_theta)
    cache = mla_cache_write(cache, c_kv_new, k_pe_new, t)

    kvb = p["kv_b"].reshape(kv_lora, n_heads, qk_nope + v_dim)
    w_uk, w_uv = kvb[..., :qk_nope], kvb[..., qk_nope:]
    # Absorb W_uk into the query:  q_c[b,h,c] = Σ_d q_nope[b,h,d] W_uk[c,h,d]
    q_c = jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk,
                     preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(qk_nope + qk_rope).astype(jnp.float32)
    # Mixed-dtype dots with fp32 accumulation: the CACHE operand stays in
    # its storage dtype (never materializing an fp32 copy of 32k slots); the
    # small query-side operands stay fp32 (CPU's DotThunk lacks some
    # bf16xbf16 contractions, and the bytes live in the cache side anyway).
    scores = (jnp.einsum("bqhc,bsc->bhqs", q_c,
                         cache.ckv, preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                           cache.kpe, preferred_element_type=jnp.float32)
              ) * scale
    q_pos = jnp.broadcast_to(pos1, (b, 1))
    mask = causal_mask(q_pos, cache.pos, window) & (cache.pos[:, None, :] >= 0)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx_c = jnp.einsum("bhqs,bsc->bqhc", w, cache.ckv,
                       preferred_element_type=jnp.float32)
    # Absorb W_uv on the way out.
    ctx = jnp.einsum("bqhc,chd->bqhd", ctx_c, w_uv.astype(jnp.float32))
    out = ctx.reshape(b, 1, n_heads * v_dim).astype(x.dtype) @ p["wo"]
    return out, cache

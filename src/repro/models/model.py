"""Config-driven model: one implementation covering all ten assigned
decoder architectures (dense GQA, MoE, MLA+MoE, Mamba/attention hybrid,
RWKV6, VLM/audio backbones) and the RoBERTa encoder (bidirectional
attention, post-LN blocks, learned positions, a classification head read at
the ``<s>`` row; static choices on ``ArchConfig``).

Layers are grouped into the config's repeating block (``block_period``) and
executed with ``lax.scan`` over stacked block parameters — compile time stays
flat in depth (72-layer Jamba lowers as one scanned block of 8), and
activation rematerialization wraps the scanned body.

Three entry points, matching the input-shape matrix:
  * ``loss_fn``      — next-token CE training step objective (train_4k);
                       an encoder's class CE at the ``<s>`` row
  * ``prefill``      — full-sequence forward that fills decode caches (prefill_32k)
  * ``decode_step``  — one token with KV cache / recurrent state
                       (decode_32k, long_500k)

An encoder has no decode: ``init_decode_state`` refuses it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_lib
from . import mamba as mamba_lib
from . import moe as moe_lib
from . import rwkv as rwkv_lib
from .layers import (LowRankDelta, apply_norm, constrain, dense_init, glu_mlp,
                     glu_mlp_init, mlp, mlp_init, norm_init,
                     sinusoidal_positions)
from ..configs.base import ArchConfig

PyTree = Any


# ------------------------------------------------------------------ init ----

def _init_layer(key, cfg: ArchConfig, mix: str, ffn: str) -> Dict:
    dtype = cfg.param_dtype
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.d_model, cfg.norm)}
    if mix == "attn":
        p["attn"] = attn_lib.gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.hd, cfg.qkv_bias,
                                      dtype, out_bias=cfg.proj_bias)
    elif mix == "mla":
        p["attn"] = attn_lib.mla_init(
            ks[0], cfg.d_model, cfg.n_heads, q_lora=cfg.q_lora_rank,
            kv_lora=cfg.kv_lora_rank, qk_nope=cfg.qk_nope_dim,
            qk_rope=cfg.qk_rope_dim, v_dim=cfg.v_head_dim, dtype=dtype)
    elif mix == "mamba":
        p["mamba"] = mamba_lib.mamba_init(
            ks[0], cfg.d_model, expand=cfg.mamba_expand,
            d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv, dtype=dtype)
    elif mix == "rwkv":
        p["tmix"] = rwkv_lib.time_mix_init(ks[0], cfg.d_model, dtype)

    p["norm2"] = norm_init(cfg.d_model, cfg.norm)
    if ffn == "moe":
        p["moe"] = moe_lib.moe_init(ks[1], cfg.d_model, cfg.n_experts,
                                    cfg.moe_d_ff or cfg.d_ff,
                                    cfg.n_shared_experts,
                                    dtype=dtype)
    elif ffn == "cmix":
        p["cmix"] = rwkv_lib.channel_mix_init(ks[1], cfg.d_model, cfg.d_ff,
                                              dtype)
    elif cfg.mlp_kind == "glu":
        p["mlp"] = glu_mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype,
                            bias=cfg.proj_bias)
    if cfg.fp32_weights:
        for g in ("attn", "mlp"):
            if g in p:
                p[g] = {k: v.astype(jnp.float32) if v.ndim == 2 else v
                        for k, v in p[g].items()}
    return p


def init_params(key: jax.Array, cfg: ArchConfig) -> PyTree:
    dtype = cfg.param_dtype
    kinds = cfg.layer_kinds()
    period, n_blocks = cfg.block_period(), cfg.n_blocks()
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    blocks = []
    for j in range(period):
        mix, ffn = kinds[j]
        keys = jax.random.split(jax.random.fold_in(k_layers, j), n_blocks)
        stacked = jax.vmap(lambda kk: _init_layer(kk, cfg, mix, ffn))(keys)
        blocks.append(stacked)

    d = cfg.d_model
    embed = {"w": dense_init(k_embed, (cfg.vocab_size, d), dtype=dtype)}
    if cfg.pos_emb == "learned":
        embed["pos"] = dense_init(jax.random.fold_in(k_embed, 1),
                                  (cfg.max_positions, d), dtype=dtype)
    if cfg.type_vocab_size:
        embed["type"] = dense_init(jax.random.fold_in(k_embed, 2),
                                   (cfg.type_vocab_size, d), dtype=dtype)
    if cfg.embed_norm:
        embed["norm"] = norm_init(d, cfg.norm)
    params = {"embed": embed, "blocks": blocks}
    if not cfg.post_norm:
        params["final_norm"] = norm_init(d, cfg.norm)
    if cfg.n_classes:
        k_dense, k_out = jax.random.split(k_head)
        params["cls_head"] = {
            "dense": {"w": dense_init(k_dense, (d, d), dtype=dtype),
                      "b": jnp.zeros((d,), dtype)},
            "out_proj": {"w": dense_init(k_out, (d, cfg.n_classes),
                                         dtype=dtype),
                         "b": jnp.zeros((cfg.n_classes,), dtype)}}
    elif not cfg.tie_embeddings:
        params["lm_head"] = {"w": dense_init(k_head, (d, cfg.vocab_size),
                                             dtype=dtype)}
    return params



def _scan_blocks(cfg: ArchConfig, body, carry, xs):
    """lax.scan over stacked blocks, or a Python loop when cfg.unroll_blocks
    (straight-line HLO for accurate cost_analysis — see ArchConfig)."""
    if not cfg.unroll_blocks:
        return jax.lax.scan(body, carry, xs)
    n = cfg.n_blocks()
    ys = []
    for i in range(n):
        xs_i = jax.tree_util.tree_map(lambda x: x[i], xs)
        carry, y = body(carry, xs_i)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    return carry, stacked


# --------------------------------------------------------------- forward ----

def _compute_view(lp, cfg: ArchConfig):
    """A layer's weights as the forward reads them: fp32 master projection
    matrices (``cfg.fp32_weights``), plain or as the base of a lift-free
    ``LowRankDelta``, in the compute dtype; everything else as stored."""
    if not cfg.fp32_weights:
        return lp
    dt = cfg.param_dtype

    def read(x):
        if isinstance(x, LowRankDelta):
            return x._replace(w=x.w.astype(dt))
        return x.astype(dt) if x.ndim >= 2 else x

    return jax.tree_util.tree_map(
        read, lp, is_leaf=lambda x: isinstance(x, LowRankDelta))


def _residual(h, out, norm_p, cfg: ArchConfig):
    """Pre-norm ``h + out``; post-norm (RoBERTa) ``norm(h + out)``, the sum
    and the norm in fp32 under the ``model.post_norm`` scope."""
    if not cfg.post_norm:
        return h + out
    with jax.named_scope("model.post_norm"):
        s = h.astype(jnp.float32) + out.astype(jnp.float32)
        return apply_norm(s, norm_p, cfg.norm).astype(h.dtype)


def _apply_mixer(lp, cfg: ArchConfig, mix: str, h, positions):
    x = h if cfg.post_norm else apply_norm(h, lp["norm1"], cfg.norm)
    if mix == "attn":
        out, _ = attn_lib.gqa_forward(
            lp["attn"], x, positions, n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads, head_dim=cfg.hd, rope=(cfg.pos_emb == "rope"),
            rope_theta=cfg.rope_theta, window=cfg.sliding_window,
            attn_chunk=cfg.attn_chunk, causal=cfg.causal)
    elif mix == "mla":
        out, _ = attn_lib.mla_forward(
            lp["attn"], x, positions, n_heads=cfg.n_heads,
            qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
            kv_lora=cfg.kv_lora_rank, v_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta, window=cfg.sliding_window,
            attn_chunk=cfg.attn_chunk)
    elif mix == "mamba":
        out = mamba_lib.mamba_forward(
            lp["mamba"], x, d_model=cfg.d_model, expand=cfg.mamba_expand,
            d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv)
    else:  # rwkv
        st = rwkv_lib.rwkv_state_init(x.shape[0], cfg.d_model)
        out = rwkv_lib.time_mix_forward(lp["tmix"], x, st, cfg.d_model)
    return _residual(h, out, lp["norm1"], cfg)


def _apply_ffn(lp, cfg: ArchConfig, ffn: str, h):
    x = h if cfg.post_norm else apply_norm(h, lp["norm2"], cfg.norm)
    aux = jnp.zeros([], jnp.float32)
    if ffn == "moe":
        out, aux = moe_lib.moe_forward(lp["moe"], x,
                                       k=cfg.experts_per_token, act=cfg.act,
                                       capacity_factor=cfg.capacity_factor)
    elif ffn == "cmix":
        st = rwkv_lib.rwkv_state_init(x.shape[0], cfg.d_model)
        out = rwkv_lib.channel_mix_forward(lp["cmix"], x, st)
    elif cfg.mlp_kind == "glu":
        out = glu_mlp(lp["mlp"], x, cfg.act)
    else:
        out = mlp(lp["mlp"], x, cfg.act)
    return _residual(h, out, lp["norm2"], cfg), aux


def _embed(params, cfg: ArchConfig, tokens, embeds):
    # Anchor the activation sharding right after the table gather — gathers
    # from a (model, data)-sharded table are where SPMD otherwise loses the
    # batch/client partitioning (§Perf iteration A).
    emb = params["embed"]
    h = constrain(emb["w"][tokens], "batch", None, None)
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
    if cfg.pos_emb == "sinusoidal":
        pos = jnp.arange(h.shape[1])
        h = h + sinusoidal_positions(pos, cfg.d_model)[None].astype(h.dtype)
    if cfg.pos_emb == "learned" or cfg.type_vocab_size or cfg.embed_norm:
        # RoBERTa: word + position (from pos_offset) + token type 0, then
        # the embedding norm, summed in fp32.
        s = h.astype(jnp.float32)
        if cfg.pos_emb == "learned":
            pos = cfg.pos_offset + jnp.arange(h.shape[1])
            s = s + emb["pos"][pos][None].astype(jnp.float32)
        if cfg.type_vocab_size:
            s = s + emb["type"][0].astype(jnp.float32)
        if cfg.embed_norm:
            s = apply_norm(s, emb["norm"], cfg.norm)
        h = s.astype(h.dtype)
    return h


@jax.named_scope("model.head")
def _logits(params, cfg: ArchConfig, h):
    if not cfg.post_norm:           # a post-LN stack ends normalized
        h = apply_norm(h, params["final_norm"], cfg.norm)
    w = (params["embed"]["w"].T if cfg.tie_embeddings
         else params["lm_head"]["w"])
    # vocab-sharded logits, batch/client pinned (under the fed-train vmap the
    # spmd_axis_name prepends the client axis to this constraint).
    return constrain((h @ w).astype(jnp.float32), "batch", None, "model")


@jax.named_scope("model.head")
def _classify(params, cfg: ArchConfig, h):
    """RobertaForSequenceClassification's head at the ``<s>`` row: dense,
    tanh, ``out_proj``. (B, n_classes) fp32 logits."""
    hp = params["cls_head"]
    x = h[:, 0].astype(jnp.float32)
    y = jnp.tanh(x @ hp["dense"]["w"].astype(jnp.float32)
                 + hp["dense"]["b"].astype(jnp.float32))
    return (y @ hp["out_proj"]["w"].astype(jnp.float32)
            + hp["out_proj"]["b"].astype(jnp.float32))


def forward(params: PyTree, cfg: ArchConfig, tokens: jnp.ndarray,
            embeds: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward (causal, or bidirectional for an encoder).
    Returns (logits fp32, moe aux loss): (B, L, vocab) logits, or an
    encoder's (B, n_classes) class logits."""
    kinds = cfg.layer_kinds()[: cfg.block_period()]
    h = _embed(params, cfg, tokens, embeds)
    positions = jnp.arange(h.shape[1])

    def block_body(carry, block_params):
        h, aux = carry
        for j, (mix, ffn) in enumerate(kinds):
            lp = _compute_view(block_params[j], cfg)
            h = _apply_mixer(lp, cfg, mix, h, positions)
            h, a = _apply_ffn(lp, cfg, ffn, h)
            aux = aux + a
        return (h, aux), None

    body = jax.checkpoint(block_body) if cfg.remat else block_body
    (h, aux), _ = _scan_blocks(cfg, body, (h, jnp.zeros([], jnp.float32)),
                               params["blocks"])
    if cfg.n_classes:
        return _classify(params, cfg, h), aux
    return _logits(params, cfg, h), aux


def loss_fn(params: PyTree, cfg: ArchConfig, batch: Dict,
            aux_coef: float = 0.01) -> jnp.ndarray:
    """Next-token cross-entropy; labels == -1 are masked (e.g. frontend
    positions in VLM batches). An encoder's (``n_classes``): cross-entropy
    of the (B,) class ids in ``labels`` at the ``<s>`` row."""
    logits, aux = forward(params, cfg, batch["tokens"], batch.get("embeds"))
    if cfg.n_classes:
        with jax.named_scope("model.head"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, batch["labels"][:, None],
                                       axis=-1)[:, 0]
            return jnp.mean(nll) + aux_coef * aux
    labels = batch["labels"]
    n_front = logits.shape[1] - labels.shape[1]
    if n_front:
        logits = logits[:, n_front:]
    with jax.named_scope("model.head"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = labels >= 0
        safe = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return ce + aux_coef * aux


# ---------------------------------------------------------------- decode ----

class DecodeState(NamedTuple):
    t: jnp.ndarray          # int32 absolute position: scalar (homogeneous
                            # batch) or (B,) per-slot (continuous batching)
    layers: PyTree          # list (period) of stacked per-block states


def _layer_state_init(cfg: ArchConfig, mix: str, batch: int, cache_len: int):
    if mix == "attn":
        return attn_lib.kv_cache_init(batch, cache_len, cfg.n_kv_heads, cfg.hd)
    if mix == "mla":
        return attn_lib.mla_cache_init(batch, cache_len, cfg.kv_lora_rank,
                                       cfg.qk_rope_dim)
    if mix == "mamba":
        return mamba_lib.mamba_state_init(
            batch, cfg.d_model, expand=cfg.mamba_expand,
            d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv)
    return rwkv_lib.rwkv_state_init(batch, cfg.d_model)


def require_decoder(cfg: ArchConfig) -> None:
    """Refuse a configuration that has no decode: a bidirectional encoder
    (or any model with a classification head instead of an LM head)."""
    if not cfg.causal or cfg.n_classes:
        raise ValueError(
            f"{cfg.name} is a bidirectional encoder with a classification "
            "head: it has no decode, so it cannot be served token by token "
            "(train or classify it through loss_fn / forward)")


def init_decode_state(cfg: ArchConfig, batch: int, cache_len: int,
                      per_slot: bool = False) -> DecodeState:
    """cache_len: KV slots. For sliding-window archs pass the window size —
    the ring buffer keeps memory O(window) at any context length.
    ``per_slot`` starts ``t`` as a (B,) vector — each batch row advances at
    its own depth (the continuous-batching slot layout)."""
    require_decoder(cfg)
    kinds = cfg.layer_kinds()[: cfg.block_period()]
    n_blocks = cfg.n_blocks()
    layers = []
    for mix, _ in kinds:
        one = _layer_state_init(cfg, mix, batch, cache_len)
        layers.append(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_blocks,) + x.shape), one))
    t = jnp.zeros((batch,) if per_slot else [], jnp.int32)
    return DecodeState(t=t, layers=layers)


def _mixer_decode(lp, st, cfg: ArchConfig, mix: str, h, t):
    x = apply_norm(h, lp["norm1"], cfg.norm)
    if mix == "attn":
        out, st = attn_lib.gqa_decode(
            lp["attn"], x, st, t, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd, rope=(cfg.pos_emb == "rope"), rope_theta=cfg.rope_theta,
            window=cfg.sliding_window)
    elif mix == "mla":
        out, st = attn_lib.mla_decode(
            lp["attn"], x, st, t, n_heads=cfg.n_heads,
            qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
            kv_lora=cfg.kv_lora_rank, v_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta, window=cfg.sliding_window)
    elif mix == "mamba":
        out, st = mamba_lib.mamba_decode(
            lp["mamba"], x, st, d_model=cfg.d_model, expand=cfg.mamba_expand,
            d_state=cfg.mamba_d_state, d_conv=cfg.mamba_d_conv)
    else:
        out, st = rwkv_lib.time_mix_forward(lp["tmix"], x, st, cfg.d_model,
                                            return_state=True)
    return h + out, st


def _ffn_decode(lp, st, cfg: ArchConfig, ffn: str, h):
    x = apply_norm(h, lp["norm2"], cfg.norm)
    if ffn == "moe":
        out, _ = moe_lib.moe_forward(lp["moe"], x, k=cfg.experts_per_token,
                                     act=cfg.act,
                                     capacity_factor=cfg.capacity_factor)
    elif ffn == "cmix":
        out, st = rwkv_lib.channel_mix_forward(lp["cmix"], x, st,
                                               return_state=True)
    elif cfg.mlp_kind == "glu":
        out = glu_mlp(lp["mlp"], x, cfg.act)
    else:
        out = mlp(lp["mlp"], x, cfg.act)
    return h + out, st


def decode_step(params: PyTree, cfg: ArchConfig, token: jnp.ndarray,
                state: DecodeState) -> Tuple[jnp.ndarray, DecodeState]:
    """One new token for every sequence in the batch. token (B,) int32."""
    kinds = cfg.layer_kinds()[: cfg.block_period()]
    h = params["embed"]["w"][token][:, None, :]      # (B, 1, D)
    if cfg.pos_emb == "sinusoidal":
        if jnp.ndim(state.t):                        # (B,) per-slot positions
            h = h + sinusoidal_positions(state.t[:, None],
                                         cfg.d_model).astype(h.dtype)
        else:
            pos = state.t[None]
            h = h + sinusoidal_positions(pos, cfg.d_model)[None].astype(h.dtype)

    def block_body(h, xs):
        block_params, block_state = xs
        new_states = []
        for j, (mix, ffn) in enumerate(kinds):
            lp, st = _compute_view(block_params[j], cfg), block_state[j]
            h, st = _mixer_decode(lp, st, cfg, mix, h, state.t)
            h, st = _ffn_decode(lp, st, cfg, ffn, h)
            new_states.append(st)
        return h, new_states

    h, new_layers = _scan_blocks(cfg, block_body, h,
                                 (params["blocks"], state.layers))
    logits = _logits(params, cfg, h)[:, 0, :]
    return logits, DecodeState(t=state.t + 1, layers=new_layers)


def prefill(params: PyTree, cfg: ArchConfig, tokens: jnp.ndarray,
            state: DecodeState,
            embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, DecodeState]:
    """Process a prompt, filling caches. Returns (last-position logits, state).

    Assumes a fresh state (t=0) and prompt length ≤ cache size for attention
    archs (ring-buffer semantics cover the sliding-window case).
    """
    kinds = cfg.layer_kinds()[: cfg.block_period()]
    h = _embed(params, cfg, tokens, embeds)
    l_total = h.shape[1]
    positions = jnp.arange(l_total)

    def block_body(h, xs):
        block_params, block_state = xs
        new_states = []
        for j, (mix, ffn) in enumerate(kinds):
            lp, st = _compute_view(block_params[j], cfg), block_state[j]
            x = apply_norm(h, lp["norm1"], cfg.norm)
            if mix == "attn":
                out, (k, v) = attn_lib.gqa_forward(
                    lp["attn"], x, positions, n_heads=cfg.n_heads,
                    n_kv=cfg.n_kv_heads, head_dim=cfg.hd, rope=(cfg.pos_emb == "rope"),
                    rope_theta=cfg.rope_theta, window=cfg.sliding_window,
                    attn_chunk=cfg.attn_chunk)
                st = attn_lib.kv_cache_write(st, k, v, 0)
            elif mix == "mla":
                out, (ckv, kpe) = attn_lib.mla_forward(
                    lp["attn"], x, positions, n_heads=cfg.n_heads,
                    qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
                    kv_lora=cfg.kv_lora_rank, v_dim=cfg.v_head_dim,
                    rope_theta=cfg.rope_theta, window=cfg.sliding_window,
                    attn_chunk=cfg.attn_chunk)
                st = attn_lib.mla_cache_write(st, ckv, kpe, 0)
            elif mix == "mamba":
                out, st = mamba_lib.mamba_forward(
                    lp["mamba"], x, st, d_model=cfg.d_model,
                    expand=cfg.mamba_expand, d_state=cfg.mamba_d_state,
                    d_conv=cfg.mamba_d_conv, return_state=True)
            else:
                out, st = rwkv_lib.time_mix_forward(
                    lp["tmix"], x, st, cfg.d_model, return_state=True)
            h = h + out
            h, st = _ffn_decode(lp, st, cfg, ffn, h)
            new_states.append(st)
        return h, new_states

    h, new_layers = _scan_blocks(cfg, block_body, h,
                                 (params["blocks"], state.layers))
    logits = _logits(params, cfg, h[:, -1:, :])[:, 0, :]
    return logits, DecodeState(t=jnp.asarray(l_total, jnp.int32),
                               layers=new_layers)

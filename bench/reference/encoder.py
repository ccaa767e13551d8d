"""Plain reference of the RoBERTa encoder with its sequence-classification
head, in float32 at ``highest`` matmul precision, with no kernels or
batching: the published architecture (``modeling_roberta``), without
dropout and without a padding mask (every row is full length).

It imports nothing of the program; the matmul with its float8 control and
the LayerNorm are ``reference/model.py``'s. It reads weights by their names
in the layout the benchmark makes them (``bench/common.make_weights``): an
``embed`` group (word table ``w``, position table ``pos``, token-type table
``type``, LayerNorm ``norm``), one stacked block of per-layer leaves
(``attn`` wq wk wv wo with biases bq bk bv bo, ``mlp`` w_up w_down with
biases b_up b_down, ``norm1`` ``norm2``) and ``cls_head`` (``dense`` and
``out_proj``, each ``w`` and ``b``).

The equations:

- embeddings: LN(word[tok] + pos[pos_offset + i] + type[0]);
- each layer, post-norm: a = LN₁(h + Attn(h)),
  h' = LN₂(a + gelu(a W_up + b_up) W_down + b_down), with the exact erf
  GELU and LayerNorm eps 1e-5;
- attention: bidirectional (every query sees every key), multi-head,
  scores scaled by 1/sqrt(head dim), biases on q, k, v and o;
- head, at the ``<s>`` row only: tanh(h₀ W_d + b_d) W_o + b_o;
- loss: the mean cross-entropy of one class id per row.

``mode='fp8'`` is the control, as in ``reference/model.py``. Layers are
recomputed in the backward pass (remat), which changes no number.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import jax.scipy.special

from .model import einsum, layer_norm, target_names  # noqa: F401


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _f32(x):
    return x.astype(jnp.float32)


def forward(params: Dict, arch: Dict, tokens, mode: str = "f32",
            targets: Optional[Dict] = None):
    """Class logits (B, n_classes) float32. ``targets`` maps
    ``attn/wq``-style names to stacked (layers, m, n) weights that replace
    the stored ones."""
    targets = targets or {}
    d, nh = arch["d_model"], arch["n_heads"]
    hd = d // nh
    b, l = tokens.shape
    emb = params["embed"]
    pos = arch["pos_offset"] + jnp.arange(l)
    h = _f32(emb["w"])[tokens] + _f32(emb["pos"])[pos][None] \
        + _f32(emb["type"])[0]
    h = layer_norm(h, emb["norm"])

    blk = params["blocks"][0]
    layer_w = {f"{g}/{n}": targets.get(f"{g}/{n}", blk[g][n])
               for g in ("attn", "mlp") for n in blk[g]}
    layer_w["norm1"] = blk["norm1"]
    layer_w["norm2"] = blk["norm2"]

    def body(h, lw):
        def proj(x, w, bias):
            return einsum("bld,de->ble", x, lw[w], mode) + _f32(lw[bias])

        q = proj(h, "attn/wq", "attn/bq").reshape(b, l, nh, hd)
        k = proj(h, "attn/wk", "attn/bk").reshape(b, l, nh, hd)
        v = proj(h, "attn/wv", "attn/bv").reshape(b, l, nh, hd)
        s = einsum("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
        p = jax.nn.softmax(s, axis=-1)
        ctx = einsum("bhqk,bkhd->bqhd", p, v, mode).reshape(b, l, d)
        a = layer_norm(h + proj(ctx, "attn/wo", "attn/bo"), lw["norm1"])
        u = gelu_erf(proj(a, "mlp/w_up", "mlp/b_up"))
        h = layer_norm(a + proj(u, "mlp/w_down", "mlp/b_down"), lw["norm2"])
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, layer_w)
    hp = params["cls_head"]
    y = jnp.tanh(einsum("bd,de->be", h[:, 0], hp["dense"]["w"], mode)
                 + _f32(hp["dense"]["b"]))
    return (einsum("bd,dc->bc", y, hp["out_proj"]["w"], mode)
            + _f32(hp["out_proj"]["b"]))


def loss(params, arch, tokens, labels, mode: str = "f32", targets=None):
    """Mean cross-entropy of the (B,) class ids ``labels``."""
    logp = jax.nn.log_softmax(forward(params, arch, tokens, mode, targets),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

"""Plain reference of the decoder the configurations describe, in float32
at ``highest`` matmul precision, with no kernels, cache or batching.

It imports nothing of the program. It reads weights by their names in the
layout the benchmark makes them (``bench/common.make_weights``): an
``embed`` table, one stacked block of per-layer leaves (``attn`` wq wk wv
wo with optional bq bk bv, ``mlp`` w_gate w_up w_down or w_up w_down,
``norm1`` ``norm2``), ``final_norm`` and, when untied, ``lm_head``.

The layer equations are the configuration's: pre-norm residual blocks;
RMSNorm (eps 1e-6) or LayerNorm (eps 1e-5); rotary positions on the two
halves of each head (``rope_theta``) or sinusoidal positions added to the
embedding; causal multi-head attention with scores scaled by 1/sqrt(head
dim); a gated SiLU MLP or a plain tanh-GELU MLP; a tied or separate head.

``mode='fp8'`` is the control: every matmul operand is scaled per tensor
to the e4m3 range and rounded to float8, and the gradients that flow back
through a product are rounded to e5m2 the same way: the step below the
bfloat16 the configurations state. Layers are recomputed in the backward
pass (remat), which changes no number.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


E5M2_MAX = 57344.0


def _quantize(x, dtype, top):
    """Round x to a float8 type after scaling its largest magnitude to the
    type's largest value (per-tensor scaling, as fp8 training does). The
    cast saturates: the scaled value is clipped to the type's range first,
    since a value a rounding step above it casts to NaN (e4m3fn has no
    infinity) or to infinity (e5m2)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    y = jnp.clip(x / s, -top, top)
    return y.astype(dtype).astype(jnp.float32) * s


def _q_fwd(x):
    return _quantize(x, jnp.float8_e4m3fn, E4M3_MAX)


def _q_grad(x):
    return _quantize(x, jnp.float8_e5m2, E5M2_MAX)


@jax.custom_vjp
def _fp8_operand(x):
    """Forward operands in e4m3; the gradient that flows back through the
    operand is rounded to e5m2 (both per-tensor scaled)."""
    return _q_fwd(x)


def _fp8_operand_fwd(x):
    return _q_fwd(x), None


def _fp8_operand_bwd(_, g):
    return (_q_grad(g),)


_fp8_operand.defvjp(_fp8_operand_fwd, _fp8_operand_bwd)


@jax.custom_vjp
def _fp8_output(y):
    """Identity forward; the incoming gradient of a product's output is
    rounded to e5m2 before the backward products use it."""
    return y


_fp8_output.defvjp(lambda y: (y, None), lambda _, g: (_q_grad(g),))


def einsum(spec, a, b, mode: str = "f32"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8_operand(a), _fp8_operand(b)
        return _fp8_output(jnp.einsum(spec, a, b, precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, p):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + 1e-6) * p["scale"].astype(jnp.float32)


def layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + 1e-5) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, pos, theta):
    """x (B, L, H, hd); rotation of the first half against the second."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinusoidal(pos, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def forward(params: Dict, arch: Dict, tokens, mode: str = "f32",
            targets: Optional[Dict] = None):
    """Logits (B, L, vocab) float32. ``targets`` maps ``attn/wq``-style
    names to stacked (layers, m, n) weights that replace the stored ones
    (the reference's own effective weights)."""
    targets = targets or {}
    d, nh = arch["d_model"], arch["n_heads"]
    hd = arch.get("head_dim") or d // nh
    norm = rms_norm if arch["norm"] == "rmsnorm" else layer_norm
    blk = params["blocks"][0]
    b, l = tokens.shape
    pos = jnp.arange(l)
    h = params["embed"]["w"].astype(jnp.float32)[tokens]
    if arch["pos_emb"] == "sinusoidal":
        h = h + sinusoidal(pos, d)[None]
    mask = pos[:, None] >= pos[None, :]

    def w(group, name):
        key = f"{group}/{name}"
        return targets[key] if key in targets else blk[group][name]

    layer_w = {f"{g}/{n}": w(g, n) for g in ("attn", "mlp")
               for n in blk[g]}
    layer_w["norm1"] = blk["norm1"]
    layer_w["norm2"] = blk["norm2"]

    def body(h, lw):
        x = norm(h, lw["norm1"])
        q = einsum("bld,de->ble", x, lw["attn/wq"], mode)
        k = einsum("bld,de->ble", x, lw["attn/wk"], mode)
        v = einsum("bld,de->ble", x, lw["attn/wv"], mode)
        if "attn/bq" in lw:
            q = q + lw["attn/bq"].astype(jnp.float32)
            k = k + lw["attn/bk"].astype(jnp.float32)
            v = v + lw["attn/bv"].astype(jnp.float32)
        nkv = arch["n_kv_heads"]
        q = q.reshape(b, l, nh, hd)
        k = k.reshape(b, l, nkv, hd)
        v = v.reshape(b, l, nkv, hd)
        if arch["pos_emb"] == "rope":
            q = rope(q, pos, arch.get("rope_theta", 1e4))
            k = rope(k, pos, arch.get("rope_theta", 1e4))
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        s = einsum("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ctx = einsum("bhqk,bkhd->bqhd", p, v, mode).reshape(b, l, nh * hd)
        h = h + einsum("ble,ed->bld", ctx, lw["attn/wo"], mode)
        x = norm(h, lw["norm2"])
        if "mlp/w_gate" in lw:
            g = einsum("bld,df->blf", x, lw["mlp/w_gate"], mode)
            u = einsum("bld,df->blf", x, lw["mlp/w_up"], mode)
            a = jax.nn.silu(g) * u
        else:
            a = gelu_tanh(einsum("bld,df->blf", x, lw["mlp/w_up"], mode))
        h = h + einsum("blf,fd->bld", a, lw["mlp/w_down"], mode)
        return h, None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, layer_w)
    h = norm(h, params["final_norm"])
    if arch.get("tie_embeddings"):
        return einsum("bld,vd->blv", h, params["embed"]["w"], mode)
    return einsum("bld,dv->blv", h, params["lm_head"]["w"], mode)


def loss(params, arch, tokens, labels, mode: str = "f32", targets=None):
    """Mean cross-entropy over the labelled positions (labels >= 0)."""
    logits = forward(params, arch, tokens, mode, targets)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lab = jnp.where(labels >= 0, labels, 0)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    m = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def target_names(params) -> list:
    """The stacked matrices the federated round trains and the serving
    adapters ride on, in the order the tree flattens: every 3-D leaf under
    ``attn`` and ``mlp``, keys sorted."""
    blk = params["blocks"][0]
    return [f"{g}/{n}" for g in ("attn", "mlp") for n in sorted(blk[g])
            if blk[g][n].ndim == 3]

"""Shared building blocks: norms, activations, MLPs, embeddings, RoPE —
plus the **lift-free delta context** (:class:`LowRankDelta` / :func:`dense`)
that lets a factored federated client run its forward/backward without ever
materializing ``base_scale·W + lift(R̃)`` or a dense ``m×n`` gradient."""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as kops


def dense_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


# ------------------------------------------------- lift-free delta context --
#
# A factored client's effective weight is W_eff = scale·W + lift(R̃, B): a
# rank-r delta around the broadcast base. Materializing W_eff costs an
# O(m·n·r) lift GEMM + an O(m·n) transient per target leaf per local step,
# and AD through it produces a dense m×n cotangent that the optimizer
# immediately re-projects to rank r. Neither needs to exist: a LowRankDelta
# *replaces the weight leaf itself* inside the loss closure, and every
# `x @ w`-style read routes through `dense()` /
# `__rmatmul__`, which computes the split-matmul apply
#
#   right (m ≥ n):  y = scale·(x@W) + (x@R̃)@Bᵀ        R̃ (m, r), B (n, r)
#   left  (m < n):  y = scale·(x@W) + (x@B)@R̃          B (m, r), R̃ (r, n)
#
# under a custom_vjp whose backward emits the cotangent for R̃ **already in
# rank-r coordinates** (right: xᵀ(∂y B); left: (xB)ᵀ∂y — never the dense
# xᵀ∂y) plus an exact dense-gradient norm probe for global-norm clipping.
# Being a pytree node, the context survives `lax.scan` over stacked layer
# params, vmap over clients, and remat — each transformation just maps the
# five fields. LoRA / dense methods never construct LowRankDelta leaves, so
# `dense(x, plain_array)` is exactly `x @ w` for them.

_LOWRANK_PALLAS_OVERRIDE = [None]   # None = auto (kops.use_kernels)


class lowrank_pallas_override:
    """Force the fused ``lowrank_linear`` kernel on/off inside ``dense``
    (None = auto: ``kernels.ops.use_kernels``; tests force True to run the
    kernel in interpret mode). Usable as a context manager around
    tracing."""

    def __init__(self, flag):
        self.flag = flag

    def __enter__(self):
        _LOWRANK_PALLAS_OVERRIDE.append(self.flag)
        return self

    def __exit__(self, *exc):
        _LOWRANK_PALLAS_OVERRIDE.pop()
        return False


def _use_lowrank_pallas() -> bool:
    flag = _LOWRANK_PALLAS_OVERRIDE[-1]
    if flag is not None:
        return flag
    return kops.use_kernels()


class LowRankDelta(NamedTuple):
    """A factored target leaf: the base weight plus its never-lifted rank-r
    delta. All five fields are pytree children (arrays), so the node slices
    cleanly under ``lax.scan`` over stacked (nb, m, n) layer params."""
    w: jnp.ndarray       # (..., m, n) broadcast base weight
    basis: jnp.ndarray   # (..., n, r) right | (..., m, r) left (orthonormal)
    rt: jnp.ndarray      # (..., m, r) right | (..., r, n) left — the delta R̃
    nsq: jnp.ndarray     # (...,) zeros — dense-grad ‖·‖² probe (cotangent out)
    scale: jnp.ndarray   # (...,) base_scale = (1-ηλ)^t

    @property
    def shape(self):
        return self.w.shape

    @property
    def dtype(self):
        return self.w.dtype

    @property
    def ndim(self):
        return self.w.ndim

    @property
    def side(self) -> str:
        """proj_type=std side rule on the ambient shape (right iff m >= n)."""
        m, n = self.w.shape[-2:]
        return "right" if m >= n else "left"

    def __rmatmul__(self, x):
        """``x @ delta_leaf`` — arbitrary losses work without edits."""
        return dense(x, self)

    def read(self):
        """Materialize the effective leaf ``scale·w + lift(rt)`` for
        non-matmul consumption (e.g. stacked bias blocks added to
        activations). The custom VJP still returns the rank-r cotangent and
        the exact norm probe — here the leaf is read directly, so the dense
        gradient IS the incoming cotangent and the probe is just ``‖∂y‖²``.
        The transient lift this reintroduces is O(dim·r) for the skinny
        leaves that take this path, not the O(m·n·r) projection lift."""
        return lowrank_read(self.side, self.w, self.basis, self.rt,
                            self.nsq, self.scale)

    def __add__(self, other):
        return self.read() + other

    def __radd__(self, other):
        return other + self.read()


def _lift(rt, basis, side):
    """project_back with leading batch dims (core.projector conventions,
    inlined to keep this module dependency-free of core)."""
    if side == "right":
        return jnp.einsum("...mr,...nr->...mn", rt, basis)
    return jnp.einsum("...mr,...rn->...mn", basis, rt)


def _project(g, basis, side):
    if side == "right":
        return jnp.einsum("...mn,...nr->...mr", g, basis)
    return jnp.einsum("...mr,...mn->...rn", basis, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def lowrank_read(side, w, basis, rt, nsq, scale):
    """Materialized delta-leaf read ``scale·w + lift(rt, basis)`` — the
    fallback for target leaves consumed other than by matmul. Backward:
    cotangent for ``rt`` arrives projected (``project(∂y, B)``), the norm
    probe is the exact ``‖∂y‖²`` (the dense gradient of a directly-read leaf
    is its own cotangent)."""
    del nsq
    lead = w.shape[:-2]
    s = jnp.asarray(scale, jnp.float32).reshape(lead + (1, 1))
    out = s * w.astype(jnp.float32) + _lift(rt.astype(jnp.float32),
                                            basis.astype(jnp.float32), side)
    return out.astype(w.dtype)


def _lowrank_read_fwd(side, w, basis, rt, nsq, scale):
    return lowrank_read(side, w, basis, rt, nsq, scale), (w, basis, rt, scale)


def _lowrank_read_bwd(side, res, dy):
    w, basis, rt, scale = res
    dy32 = dy.astype(jnp.float32)
    drt = _project(dy32, basis.astype(jnp.float32), side)
    dnsq = jnp.sum(dy32 * dy32, axis=(-2, -1))
    return (jnp.zeros_like(w), jnp.zeros_like(basis), drt, dnsq,
            jnp.zeros_like(scale))


lowrank_read.defvjp(_lowrank_read_fwd, _lowrank_read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def lowrank_apply(side, use_pallas, x, w, basis, rt, nsq, scale):
    """The lift-free delta read: ``x @ (scale·w + lift(rt, basis))`` computed
    as split matmuls (fused Pallas kernel on TPU). ``nsq`` (zeros) is the
    norm probe: its cotangent is the exact squared Frobenius norm of the
    dense weight gradient ``xᵀ∂y`` — computed from token Grams, so
    global-norm clipping matches the transient-lift path bit-for-bit in
    exact arithmetic without the m×n cotangent ever existing. Caveat: AD
    sums the probe across *uses* of a leaf, so a weight read more than once
    per forward (e.g. MLA blockwise ``kv_b``, once per chunk) yields
    ``Σᵤ‖gᵤ‖²`` instead of the exact ``‖Σᵤgᵤ‖²`` — the sign-indefinite
    cross-use terms are missing, so it is neither a bound nor exact.
    ``make_fed_round_step`` gates such configurations (MLA + attn_chunk)
    off the lift-free path; every single-read weight is exact."""
    del nsq
    if use_pallas:
        return kops.lowrank_linear(x, w, basis, rt, scale, side=side)
    x32 = x.astype(jnp.float32)
    base = scale * (x32 @ w.astype(jnp.float32))
    b32 = basis.astype(jnp.float32)
    r32 = rt.astype(jnp.float32)
    delta = (x32 @ r32) @ b32.T if side == "right" else (x32 @ b32) @ r32
    return (base + delta).astype(jnp.result_type(x.dtype, w.dtype))


_SQNORM_TILE = 1024


def _sqnorm_gram(x2, dy2, tile: int = _SQNORM_TILE):
    """Exact ``‖x2ᵀ dy2‖²_F = Σᵢⱼ (x2 x2ᵀ)ᵢⱼ (dy2 dy2ᵀ)ᵢⱼ`` without the
    (m, n) product. Short token counts take one (t, t) Gram pair; longer
    ones scan over row tiles so the transient working set is O(nt·tile²)
    per step instead of O(t²) — the probe must never cost more memory than
    the m×n object it replaces. Zero-padding the tail tile is sound (zero
    rows contribute zero to both Grams)."""
    t, _ = x2.shape
    if t <= tile:
        return jnp.sum((x2 @ x2.T) * (dy2 @ dy2.T))
    nt = -(-t // tile)
    pad = nt * tile - t
    xp = jnp.pad(x2, ((0, pad), (0, 0))).reshape(nt, tile, -1)
    dyp = jnp.pad(dy2, ((0, pad), (0, 0))).reshape(nt, tile, -1)

    def row(acc, xi_dyi):
        xi, dyi = xi_dyi
        # all j-tiles against this i-tile in one batched contraction
        cx = jnp.einsum("tm,jsm->jts", xi, xp)
        cd = jnp.einsum("tn,jsn->jts", dyi, dyp)
        return acc + jnp.sum(cx * cd), None

    acc, _ = jax.lax.scan(row, jnp.zeros((), jnp.float32), (xp, dyp))
    return acc


def _lowrank_fwd(side, use_pallas, x, w, basis, rt, nsq, scale):
    y = lowrank_apply(side, use_pallas, x, w, basis, rt, nsq, scale)
    return y, (x, w, basis, rt, scale)


def _lowrank_bwd(side, use_pallas, res, dy):
    del use_pallas
    x, w, basis, rt, scale = res
    m, n = w.shape
    dy32 = dy.astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    b32 = basis.astype(jnp.float32)
    r32 = rt.astype(jnp.float32)
    # dx through the effective weight, split low-rank (never lift(rt)).
    if side == "right":
        dx = scale * (dy32 @ w.astype(jnp.float32).T) + (dy32 @ b32) @ r32.T
    else:
        dx = scale * (dy32 @ w.astype(jnp.float32).T) + (dy32 @ r32.T) @ b32.T
    # Projected cotangent for R̃ — rank-r coordinates, no dense xᵀ∂y:
    #   right: xᵀ(∂y B) (m, r);  left: (x B)ᵀ ∂y (r, n).
    x2 = x32.reshape((-1, m))
    dy2 = dy32.reshape((-1, n))
    if side == "right":
        drt = x2.T @ (dy2 @ b32)
    else:
        drt = (x2 @ b32).T @ dy2
    # Exact ‖xᵀ∂y‖²_F via token Grams: O(t²(m+n)) flops with t = tokens, no
    # m×n object, transients bounded by the token tile. DCE'd entirely when
    # the caller never reads the probe cotangent (clip_norm=None).
    with jax.named_scope("lowrank.norm_probe"):
        dnsq = _sqnorm_gram(x2, dy2)
    # w / basis / scale are never differentiated by the lift-free step; the
    # zero cotangents exist only to satisfy the VJP signature and are dead
    # code after DCE (asserted GEMM-free by the shape-probe test).
    return (dx.astype(x.dtype), jnp.zeros_like(w), jnp.zeros_like(basis),
            drt, dnsq, jnp.zeros_like(scale))


lowrank_apply.defvjp(_lowrank_fwd, _lowrank_bwd)


# ------------------------------------------- multi-adapter serving context --
#
# The serving counterpart of LowRankDelta: one shared base weight plus a
# TABLE of G adapters' factors, where each row of the batch selects its own
# adapter by the (B,) ids operand installed via `adapter_ids(...)`. The
# forward is the same split-matmul apply as the training leaf — per row:
#
#   y[b] = scales[g]·(x[b] @ W) + split-matmul(x[b], bases[g], rts[g]),
#   g = ids[b]
#
# routed through the scalar-prefetch Pallas kernel on TPU (only the selected
# adapters' blocks are DMA'd from the (G, ·, r) tables) and a gather+einsum
# reference elsewhere. Forward-only by design: serving never differentiates
# the leaf. Ragged per-adapter ranks arrive zero-padded to the table's
# r_max (zero columns contribute exactly zero delta).

_ADAPTER_IDS = [None]   # (B,) int32 adapter index per batch row


@contextlib.contextmanager
def adapter_ids(ids):
    """Install the per-row adapter-id operand consumed by ``dense`` when it
    meets a :class:`MultiAdapterDelta` leaf. The ids array is traced state:
    enter inside the same jit/scan trace that runs the forward."""
    _ADAPTER_IDS.append(None if ids is None else jnp.asarray(ids, jnp.int32))
    try:
        yield
    finally:
        _ADAPTER_IDS.pop()


class MultiAdapterDelta(NamedTuple):
    """A served target leaf: broadcast base weight plus a G-adapter factor
    table. All fields are pytree children with a common leading stack axis
    where the ambient params are stacked — (nb, m, n) bases pair with
    (nb, G, dim, r) tables, so the node slices cleanly under the model's
    ``lax.scan`` over stacked layer params."""
    w: jnp.ndarray        # (..., m, n) shared base weight
    bases: jnp.ndarray    # (..., G, n, r) right | (..., G, m, r) left
    rts: jnp.ndarray      # (..., G, m, r) right | (..., G, r, n) left
    scales: jnp.ndarray   # (..., G) per-adapter base_scale

    @property
    def shape(self):
        return self.w.shape

    @property
    def dtype(self):
        return self.w.dtype

    @property
    def ndim(self):
        return self.w.ndim

    @property
    def side(self) -> str:
        m, n = self.w.shape[-2:]
        return "right" if m >= n else "left"

    def __rmatmul__(self, x):
        """``x @ leaf`` — decode projections (``x @ p["wq"]``) route here."""
        return dense(x, self)


def multi_adapter_apply(leaf: MultiAdapterDelta, x, ids):
    """Batched heterogeneous-adapter apply for one leaf. x (B, t, m) or
    (B, m); ids (B,). The leaf must be sliced to its per-layer view (2-D
    base) by the ambient scan before application."""
    if leaf.w.ndim != 2:
        raise ValueError(
            "multi-adapter leaf applied with a stacked base "
            f"{leaf.w.shape} — expected the scan-sliced per-layer view")
    if x.shape[0] != ids.shape[0]:
        raise ValueError(
            f"adapter ids cover {ids.shape[0]} rows but the batch has "
            f"{x.shape[0]} — one id per decode row is required")
    if _use_lowrank_pallas():
        return kops.lowrank_linear_batched(x, leaf.w, leaf.bases, leaf.rts,
                                           leaf.scales, ids, side=leaf.side)
    from ..kernels.ref import lowrank_linear_batched_ref
    return lowrank_linear_batched_ref(x, leaf.w, leaf.bases, leaf.rts,
                                      leaf.scales, ids, side=leaf.side)


def dense(x, w):
    """Delta-aware linear apply: ``x @ w`` for plain weights; the lift-free
    split-matmul read (projected-cotangent backward) when ``w`` is a
    :class:`LowRankDelta` leaf; the per-row heterogeneous-adapter apply when
    ``w`` is a :class:`MultiAdapterDelta` serving leaf (batch ids from the
    ambient :func:`adapter_ids` context). Model projections route through
    this so ``loss_fn(params, batch)`` signatures never change."""
    if isinstance(w, LowRankDelta):
        # The scope also names the custom-VJP backward's ops (cotangents
        # and the norm probe), which are traced under the forward's name.
        with jax.named_scope("lowrank.apply"):
            return lowrank_apply(w.side, _use_lowrank_pallas(), x, w.w,
                                 w.basis, w.rt, w.nsq, w.scale)
    if isinstance(w, MultiAdapterDelta):
        ids = _ADAPTER_IDS[-1]
        if ids is None:
            raise ValueError(
                "MultiAdapterDelta leaf read outside an adapter_ids(...) "
                "context — the serving driver must install the per-row "
                "adapter ids around the forward")
        return multi_adapter_apply(w, x, ids)
    return x @ w


_BATCH_AXES_OVERRIDE = [None]   # None = use (pod, data) from the mesh


@contextlib.contextmanager
def batch_axes_override(axes):
    """Override (or disable, with ()) what 'batch' resolves to in constrain().

    The federated train step vmaps clients with ``spmd_axis_name`` pinning
    the CLIENT dim to the data axes; inner per-client batch constraints must
    then be disabled or they would claim the same mesh axes twice.
    """
    _BATCH_AXES_OVERRIDE.append(axes)
    try:
        yield
    finally:
        _BATCH_AXES_OVERRIDE.pop()


def constrain(x: jnp.ndarray, *spec):
    """Sharding constraint against the ambient mesh (``jax.set_mesh``):
    'batch' resolves to whichever of (pod, data) exist on it; 'model' must
    exist; an axis that does not divide its dim is dropped. A no-op when
    tracing without a mesh that has a 'model' axis (host-scale runs).

    These hints pin the batch dimension of attention intermediates — without
    them SPMD can replicate the (L, L) score tensors across the data axis
    (§Perf iteration B measured a 16× bytes regression from exactly that).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return x
    if _BATCH_AXES_OVERRIDE[-1] is not None:
        batch_axes = tuple(_BATCH_AXES_OVERRIDE[-1])
    else:
        batch_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    sizes = dict(mesh.shape)
    resolved = []
    for dim, s in zip(x.shape, spec):
        if s == "batch":
            s = batch_axes if batch_axes else None
        if s is not None:
            names = (s,) if isinstance(s, str) else tuple(s)
            total = 1
            for nm in names:
                total *= sizes[nm]
            if dim % total != 0:
                s = None
        resolved.append(s)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*resolved))


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_init(d: int, kind: str, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,                 # tanh form (starcoder2, musicgen)
    "gelu_exact": functools.partial(jax.nn.gelu, approximate=False),  # erf
    "relu": jax.nn.relu,
}


def glu_mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
            "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
            "w_down": dense_init(k3, (d_ff, d_model), dtype=dtype)}


def glu_mlp(p, x, act: str = "silu"):
    """Gated MLP (SwiGLU family) — llama/mistral/command-r style."""
    gate = ACTS[act](dense(x, p["w_gate"]))
    return dense(gate * dense(x, p["w_up"]), p["w_down"])


def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32,
             bias: bool = False):
    k1, k2 = jax.random.split(key)
    p = {"w_up": dense_init(k1, (d_model, d_ff), dtype=dtype),
         "w_down": dense_init(k2, (d_ff, d_model), dtype=dtype)}
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


def mlp(p, x, act: str = "gelu"):
    """Plain 2-layer MLP (starcoder2 / musicgen style; RoBERTa's with the
    biases). A bias is a frozen leaf added to the ``dense`` output, so a
    lift-free target keeps its kernel and the bias rides outside it."""
    up = dense(x, p["w_up"])
    if "b_up" in p:
        up = up + p["b_up"]
    out = dense(ACTS[act](up), p["w_down"])
    if "b_down" in p:
        out = out + p["b_down"]
    return out


# ------------------------------------------------------------------ RoPE ----

def rope_freqs(head_dim: int, theta: float = 1e4) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) absolute."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Any-length sinusoidal embeddings (musicgen — no learned table)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

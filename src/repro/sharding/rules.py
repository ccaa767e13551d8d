"""Sharding rules: param/state tree paths -> PartitionSpec.

Megatron-style tensor parallelism over the ``model`` axis plus FSDP-style
weight sharding over the ``data`` axis (ZeRO-3; XLA inserts the per-layer
all-gathers). The ``pod`` axis is pure data/client parallelism — parameters
replicate across pods, so the only cross-pod traffic is the gradient /
federated-aggregation all-reduce, matching the paper's round structure.

Every rule degrades gracefully: an axis is only assigned to a dimension it
divides, so any (arch × mesh) combination lowers. Rules:

  COL  (d_in, d_out)        -> P(fsdp, model)       wq/wk/wv/w_gate/w_up/...
  ROW  (d_in, d_out)        -> P(model, fsdp)       wo/w_down/out_proj/...
  EXP  (E, d_in, d_out)     -> P(model, fsdp, None) expert-parallel MoE
  EMB  (V, D)               -> P(model, fsdp)       embeddings / lm head
  REPL                      -> P()                  norms, biases, routers

Stacked scan-block leaves get a leading None. GaLore states follow their
block's rule on the ambient dim (basis (n, r) of a COL block shards n over
model iff the block's n was model-sharded; projected buffers (m, r) follow m).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# path-suffix -> rule name
_RULES: Tuple[Tuple[str, str], ...] = (
    (r"embed/w$", "emb"),
    (r"lm_head/w$", "emb_t"),
    (r"moe/router$", "repl"),
    (r"moe/w_(gate|up)$", "exp_col"),
    (r"moe/w_down$", "exp_row"),
    (r"shared/w_(gate|up)$", "col"),
    (r"shared/w_down$", "row"),
    (r"(attn/w[qkv]|attn/q_a|attn/q_b|attn/kv_a|attn/kv_b)$", "col"),
    (r"attn/wo$", "row"),
    (r"mlp/w_(gate|up)$", "col"),
    (r"mlp/w_down$", "row"),
    (r"mamba/(in_proj|dt_proj)$", "col"),
    (r"mamba/(out_proj|x_proj)$", "row"),
    (r"mamba/conv_w$", "conv"),
    (r"mamba/(a_log|d_skip)$", "inner_vec"),
    (r"tmix/(wr|wk|wv|wg|maa_w1|decay_w1)$", "col"),
    (r"tmix/(wo|maa_w2|decay_w2)$", "row_last2"),
    (r"cmix/(wk|wr)$", "col"),
    (r"cmix/wv$", "row"),
)


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    if axes is None:
        return True
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return dim % size == 0


def _guard(shape, mesh: Mesh, spec_dims) -> P:
    """Drop any axis that does not divide its dimension."""
    out = []
    for dim, axes in zip(shape, spec_dims):
        out.append(axes if _fits(dim, mesh, axes) else None)
    return P(*out)


def auto_axes(mesh):
    """``mesh`` with every axis in Auto mode. The program places arrays with
    these rules and steers propagation with sharding constraints, which is
    Auto-mode sharding; on an Explicit mesh (``jax.make_mesh``'s default)
    every op would instead type its own output sharding, and the embedding
    gather alone asks for ``P('data', None, 'data')``."""
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if not isinstance(mesh, Mesh) or tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def path_of(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


class ShardingRules:
    """Resolves PartitionSpecs against a concrete mesh.

    data_axis: FSDP/weight-sharding axis name(s); model_axis: TP axis;
    batch_axes: axes used for the batch dim of activations/inputs
    (('pod','data') on the multi-pod mesh).
    """

    def __init__(self, mesh: Mesh, data_axis: str = "data",
                 model_axis: str = "model", fsdp: bool = True):
        self.mesh = auto_axes(mesh)
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.fsdp = fsdp
        self.batch_axes = tuple(n for n in ("pod", "data") if n in mesh.shape)

    # ---------------------------------------------------------- params -----
    def _rule_spec(self, rule: str, shape) -> P:
        d, m = (self.data_axis if self.fsdp else None), self.model_axis
        lead = len(shape) - 2
        if rule == "exp_col" or rule == "exp_row":
            lead = len(shape) - 3
        pre = (None,) * max(lead, 0)
        tail2 = shape[-2:]
        if rule == "col":
            return _guard(shape, self.mesh, pre + (d, m))
        if rule == "row":
            return _guard(shape, self.mesh, pre + (m, d))
        if rule == "row_last2":
            return _guard(shape, self.mesh, pre + (m, None))
        if rule == "exp_col":
            return _guard(shape, self.mesh, pre + (m, d, None))
        if rule == "exp_row":
            return _guard(shape, self.mesh, pre + (m, None, d))
        if rule == "emb":
            return _guard(shape, self.mesh, (m, d))
        if rule == "emb_t":
            return _guard(shape, self.mesh, (d, m))
        if rule == "conv":
            return _guard(shape, self.mesh, pre + (None, m))
        if rule == "inner_vec":
            # a_log (..., d_inner, d_state): shard d_inner; d_skip (..., d_inner)
            if len(shape) >= 2 and shape[-1] < shape[-2]:
                return _guard(shape, self.mesh,
                              (None,) * (len(shape) - 2) + (m, None))
            return _guard(shape, self.mesh,
                          (None,) * (len(shape) - 1) + (m,))
        return P()

    def param_rule(self, path_str: str) -> str:
        for pat, rule in _RULES:
            if re.search(pat, path_str):
                return rule
        return "repl"

    def param_spec(self, path_str: str, shape) -> P:
        return self._rule_spec(self.param_rule(path_str), shape)

    def params_shardings(self, params: PyTree) -> PyTree:
        def one(path, leaf):
            spec = self.param_spec(path_of(path), leaf.shape)
            return NamedSharding(self.mesh, spec)
        return jax.tree_util.tree_map_with_path(one, params)

    # -------------------------------------------------- optimizer states ---
    def galore_state_shardings(self, params: PyTree, opt_state: PyTree) -> PyTree:
        """GaLore/Adam states inherit the ambient-dim sharding of their block:
        for a COL block (d_in, d_out) with right basis (d_out, r), the basis
        shards d_out over model; projected (d_in, r) buffers shard d_in over
        fsdp. Dense moments mirror the param spec. Scalars replicate."""
        from ..core.galore import DenseMoments, GaloreBlockState, GaloreState

        param_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = [self.param_spec(path_of(p), leaf.shape)
                 for p, leaf in param_leaves]

        def shard_states(opt):
            if isinstance(opt, GaloreState):
                blk_leaves, treedef = jax.tree_util.tree_flatten(
                    opt.blocks, is_leaf=lambda x: isinstance(
                        x, (GaloreBlockState, DenseMoments)))
                out = []
                for (pth, leaf), st in zip(param_leaves, blk_leaves):
                    spec = self.param_spec(path_of(pth), leaf.shape)
                    dims = list(spec) + [None] * (leaf.ndim - len(spec))
                    if isinstance(st, GaloreBlockState):
                        lead = tuple(dims[:-2])
                        row_ax, col_ax = dims[-2], dims[-1]
                        right = st.m.shape[-1] == st.basis.shape[-1] and \
                            st.m.shape[-2] == leaf.shape[-2]
                        if right:
                            basis_spec = _guard(st.basis.shape, self.mesh,
                                                lead + (col_ax, None))
                            buf_spec = _guard(st.m.shape, self.mesh,
                                              lead + (row_ax, None))
                        else:
                            basis_spec = _guard(st.basis.shape, self.mesh,
                                                lead + (row_ax, None))
                            buf_spec = _guard(st.m.shape, self.mesh,
                                              lead + (None, col_ax))
                        out.append(GaloreBlockState(
                            basis=NamedSharding(self.mesh, basis_spec),
                            m=NamedSharding(self.mesh, buf_spec),
                            v=NamedSharding(self.mesh, buf_spec)))
                    else:
                        out.append(DenseMoments(
                            m=NamedSharding(self.mesh, _guard(
                                st.m.shape, self.mesh, dims[:st.m.ndim])),
                            v=NamedSharding(self.mesh, _guard(
                                st.v.shape, self.mesh, dims[:st.v.ndim]))))
                blocks = jax.tree_util.tree_unflatten(treedef, out)
                return GaloreState(
                    count=NamedSharding(self.mesh, P()),
                    seed=NamedSharding(self.mesh, P()),
                    blocks=blocks)
            # generic states (clip counters, lr count, adam moments on the
            # trainable tree): mirror param spec when shapes match, else repl.
            return jax.tree_util.tree_map(
                lambda x: NamedSharding(self.mesh, P()), opt)

        if isinstance(opt_state, tuple) and not hasattr(opt_state, "_fields"):
            return tuple(shard_states(s) for s in opt_state)
        return shard_states(opt_state)

    # ------------------------------------------------------- activations ---
    def batch_spec(self, shape) -> P:
        """Inputs (B, ...): shard batch over (pod, data) when divisible."""
        return _guard(shape, self.mesh,
                      (self.batch_axes,) + (None,) * (len(shape) - 1))

    def data_shardings(self, batch: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, self.batch_spec(x.shape)), batch)

    # ---------------------------------------------------- decode states ----
    def decode_state_shardings(self, state: PyTree) -> PyTree:
        """Decode-state layout (§Perf iteration C):

        KV caches (nb, B, S, ...) shard batch over (pod,data) and the CACHE
        SLOTS over model — flash-decoding-style sequence parallelism. The
        attention contraction over slots then reduces with a tiny psum of
        per-shard softmax statistics instead of all-gathering the cache
        (the baseline layout sharded head_dim, which SPMD could only realize
        by all-gathering the whole cache every step: 2 GiB/layer for
        command-r decode_32k). Recurrent states (no slot dim) shard batch
        over (pod,data) and their largest feature dim over model."""
        mesh = self.mesh
        m = self.model_axis

        def one(path, leaf):
            shape = leaf.shape
            dims = [None] * len(shape)
            if len(shape) >= 2:
                batch_dim = 1 if len(shape) > 1 else 0
                if _fits(shape[batch_dim], mesh, self.batch_axes):
                    dims[batch_dim] = self.batch_axes
                # cache slots (dim 2 of (nb, B, S, ...)) over model; the pos
                # buffer (nb, B, S) follows the same slot sharding
                if len(shape) >= 3 and shape[2] % mesh.shape[m] == 0 \
                        and shape[2] >= mesh.shape[m]:
                    dims[2] = m
                else:
                    # recurrent state: largest trailing dim over model
                    for cand in range(len(shape) - 1, batch_dim, -1):
                        if dims[cand] is None and \
                                shape[cand] % mesh.shape[m] == 0 and \
                                shape[cand] >= mesh.shape[m]:
                            dims[cand] = m
                            break
            return NamedSharding(mesh, P(*dims))

        return jax.tree_util.tree_map_with_path(one, state)

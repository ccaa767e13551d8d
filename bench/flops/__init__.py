"""Operation and byte counts from shapes, for the kernels and for whole
steps. Nothing here reads the program; sizes come from the configuration
file's ``arch`` block and the cell's traffic.

``required`` counts are what the lift-free method needs and no more, so no
implementation can read over 100% of a peak:

- forward matmuls of the target projections, at every position;
- the low-rank split apply on each target (2·t·r·(m+n) forward, the same
  again in the input gradient);
- backward input-gradient matmuls, except those of the first layer's
  q, k, v projections (their gradient would only reach the frozen
  embedding);
- the rank-r projected weight gradients (2·t·r·(m+n) per target);
- attention products over the causal half (query-key pairs with key <=
  query): 2·d per pair each for scores and context forward, twice that
  backward;
- the output head only at the labelled positions, forward and input
  gradient.

Remat recompute, the dense-norm probe, the optimizer and the aggregation
are not counted.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def head_dim(arch: Dict) -> int:
    return arch.get("head_dim") or arch["d_model"] // arch["n_heads"]


def targets(arch: Dict) -> List[Tuple[str, int, int]]:
    """(name, m, n) of each target projection of one layer."""
    d, ff = arch["d_model"], arch["d_ff"]
    q = arch["n_heads"] * head_dim(arch)
    kv = arch["n_kv_heads"] * head_dim(arch)
    out = [("attn/wk", d, kv), ("attn/wo", q, d), ("attn/wq", d, q),
           ("attn/wv", d, kv), ("mlp/w_down", ff, d)]
    if arch.get("mlp_kind", "glu") == "glu":
        out.append(("mlp/w_gate", d, ff))
    out.append(("mlp/w_up", d, ff))
    return out


def target_params(arch: Dict) -> int:
    return sum(m * n for _, m, n in targets(arch)) * arch["n_layers"]


def lowrank_rank_cost(arch: Dict, rank: int) -> int:
    """Sum over one layer's targets of r·(m+n)."""
    return sum(min(rank, m, n) * (m + n) for _, m, n in targets(arch))


def attention_pairs(length: int) -> int:
    return length * (length + 1) // 2


def round_required(arch: Dict, fed: Dict) -> float:
    """Required operations of one federated round (all clients)."""
    d, layers, vocab = arch["d_model"], arch["n_layers"], arch["vocab_size"]
    seqs = fed["clients"] * fed["local_steps"] * fed["batch"]
    tokens = seqs * fed["seq_len"]
    n_tgt = target_params(arch)
    rc = lowrank_rank_cost(arch, fed["rank"]) * layers
    first_qkv = sum(m * n for name, m, n in targets(arch)
                    if name in ("attn/wq", "attn/wk", "attn/wv"))
    fwd = 2 * n_tgt + 2 * rc
    dx = 2 * (n_tgt - first_qkv) + 2 * rc
    dw = 2 * rc
    attn = 12 * d * attention_pairs(fed["seq_len"]) * layers * seqs
    head = 4 * vocab * d * seqs          # one labelled position per row
    return float(tokens * (fwd + dx + dw) + attn + head)


def lowrank_linear(t: int, m: int, n: int, r: int,
                   clients: int = 1) -> Tuple[float, float]:
    """(operations, bytes) of one fused lift-free apply over ``clients``
    clients' ``t`` tokens each against one shared bf16 (m, n) base: bf16
    activations in and out, fp32 basis and factor per client; the base is
    read once."""
    flops = clients * (2 * t * m * n + 2 * t * r * (m + n))
    byts = 2 * m * n + clients * (2 * t * m + 2 * t * n + 4 * r * (m + n))
    return float(flops), float(byts)


def min_time(flops: float, byts: float, peak: Dict) -> float:
    """Least time on the chip: the larger of the compute and memory bounds."""
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])

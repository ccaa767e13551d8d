"""Required operations of one federated round of an encoder with a
classification head (RoBERTa): counted as ``flops.round_required`` counts a
decoder's, with two differences:

- attention over every query-key pair (bidirectional), not the causal half;
- the head at the ``<s>`` row of each sequence: its dense d×d and its
  ``out_proj`` d×n_classes products, forward and input gradient.

Nothing here reads the program; sizes come from the configuration file's
``arch`` block and the cell's traffic.
"""
from __future__ import annotations

from typing import Dict

from . import lowrank_rank_cost, target_params, targets


def round_required(arch: Dict, fed: Dict) -> float:
    """Required operations of one federated round (all clients)."""
    d, layers = arch["d_model"], arch["n_layers"]
    seqs = fed["clients"] * fed["local_steps"] * fed["batch"]
    tokens = seqs * fed["seq_len"]
    n_tgt = target_params(arch)
    rc = lowrank_rank_cost(arch, fed["rank"]) * layers
    first_qkv = sum(m * n for name, m, n in targets(arch)
                    if name in ("attn/wq", "attn/wk", "attn/wv"))
    fwd = 2 * n_tgt + 2 * rc
    dx = 2 * (n_tgt - first_qkv) + 2 * rc
    dw = 2 * rc
    attn = 12 * d * fed["seq_len"] ** 2 * layers * seqs
    head = 4 * (d * d + d * arch["n_classes"]) * seqs
    return float(tokens * (fwd + dx + dw) + attn + head)

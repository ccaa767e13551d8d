"""roberta-base [hf:FacebookAI/roberta-base] — the published bidirectional
encoder the paper fine-tunes for its NLU (GLUE) results [Liu 2019, paper §6].

12L d_model=768 12H d_ff=3072 vocab=50265: post-LN blocks with biases on
every projection, exact-erf GELU, learned absolute positions from 2 (the pad
id 1 plus one; 514 rows), one token-type row and a LayerNorm (eps 1e-5) over
the summed embeddings. ``RobertaForSequenceClassification``'s head reads the
``<s>`` row: dense 768→768, tanh, ``out_proj`` to ``n_classes`` (two, the
library's default number of labels; a task sets its own). The projection
matrices are kept in fp32, as the published checkpoint holds them, and read
in bf16: a federated round's mean update is often under half a bf16 ulp of
a weight, and bf16 storage would drop it.

Two departures, as everywhere in this repository: no dropout, and no padding
mask (every sequence is full length, so positions are 2 + index and id 1
is never treated as padding).
"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="roberta-base",
    family="dense",
    n_layers=12,
    d_model=768,
    n_heads=12,
    n_kv_heads=12,
    d_ff=3072,
    vocab_size=50265,
    qkv_bias=True,
    proj_bias=True,
    act="gelu_exact",
    mlp_kind="plain",
    norm="layernorm",
    pos_emb="learned",
    max_positions=514,
    pos_offset=2,
    type_vocab_size=1,
    embed_norm=True,
    causal=False,
    post_norm=True,
    n_classes=2,
    fp32_weights=True,
    citation="hf:FacebookAI/roberta-base",
))

"""RoBERTa-base as published: the program's encoder against a plain fp32
reference written here (matmuls at ``highest``), at a small size on seeded
random weights; plus the two properties that tell the published encoder
from the causal pre-LN decoder the rest of the registry is."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch import serve
from repro.launch.steps import galore_target_fn
from repro.core.fed import split_trainable
from repro.models import model as M

HIGHEST = jax.lax.Precision.HIGHEST
ROBERTA = get_config("roberta-base")
# Two layers at d=64 with every published mechanism; fp32 weights.
SMALL = dataclasses.replace(smoke_variant(ROBERTA), d_model=64, n_heads=4,
                            n_kv_heads=4, d_ff=128, vocab_size=97,
                            n_classes=3)


def random_weights(cfg, seed=0, std=0.2):
    """Every leaf random: matrices, tables and biases N(0, std), norm
    scales 1 + N(0, std), so no bias or norm is at its identity."""
    shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, s) in enumerate(flat):
        x = std * jax.random.normal(jax.random.fold_in(key, i), s.shape)
        if getattr(path[-1], "key", None) == "scale":
            x = 1.0 + x
        out.append(x.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------ reference ----

def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _ln(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def ref_forward(params, cfg, tokens):
    """HF ``RobertaForSequenceClassification`` without dropout: embeddings
    LN(word + pos[2 + i] + type[0]); per layer a = LN1(h + attn(h)),
    h = LN2(a + W_down gelu_erf(W_up a)); head tanh(dense(h[:, 0])) at
    out_proj."""
    e = params["embed"]
    b, l = tokens.shape
    nh, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    h = e["w"][tokens] + e["pos"][2 + jnp.arange(l)][None] + e["type"][0]
    h = _ln(h, e["norm"])
    blk = params["blocks"][0]
    for i in range(cfg.n_layers):
        at = {k: v[i] for k, v in blk["attn"].items()}
        ml = {k: v[i] for k, v in blk["mlp"].items()}
        q = (_mm("bld,de->ble", h, at["wq"]) + at["bq"]).reshape(b, l, nh, hd)
        k = (_mm("bld,de->ble", h, at["wk"]) + at["bk"]).reshape(b, l, nh, hd)
        v = (_mm("bld,de->ble", h, at["wv"]) + at["bv"]).reshape(b, l, nh, hd)
        p = jax.nn.softmax(_mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd), -1)
        ctx = _mm("bhqk,bkhd->bqhd", p, v).reshape(b, l, -1)
        a = _ln(h + _mm("ble,ed->bld", ctx, at["wo"]) + at["bo"],
                {n: x[i] for n, x in blk["norm1"].items()})
        u = _mm("bld,df->blf", a, ml["w_up"]) + ml["b_up"]
        u = 0.5 * u * (1.0 + jax.scipy.special.erf(u / math.sqrt(2.0)))
        h = _ln(a + _mm("blf,fd->bld", u, ml["w_down"]) + ml["b_down"],
                {n: x[i] for n, x in blk["norm2"].items()})
    hp = params["cls_head"]
    y = jnp.tanh(_mm("bd,de->be", h[:, 0], hp["dense"]["w"])
                 + hp["dense"]["b"])
    return _mm("bd,dc->bc", y, hp["out_proj"]["w"]) + hp["out_proj"]["b"]


def ref_loss(params, cfg, tokens, labels):
    logp = jax.nn.log_softmax(ref_forward(params, cfg, tokens), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def _inputs(cfg, seed=1, b=3, l=12):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(k1, (b, l), 0, cfg.vocab_size)
    tokens = tokens.at[:, 0].set(0)                       # <s>
    labels = jax.random.randint(k2, (b,), 0, cfg.n_classes)
    return tokens, labels


# ---------------------------------------------------------------- tests ----

def test_published_values():
    c = ROBERTA
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab_size) == \
        (12, 768, 12, 3072, 50265)
    assert (c.max_positions, c.type_vocab_size, c.pos_offset) == (514, 1, 2)
    assert c.act == "gelu_exact" and c.norm == "layernorm"
    assert not c.causal and c.post_norm and c.embed_norm
    assert c.qkv_bias and c.proj_bias and c.n_classes


def test_gelu_exact_is_the_erf_form():
    from repro.models.layers import ACTS
    x = jnp.linspace(-4.0, 4.0, 41)
    erf = 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))
    np.testing.assert_allclose(ACTS["gelu_exact"](x), erf, atol=1e-6)
    assert float(jnp.max(jnp.abs(ACTS["gelu"](x) - erf))) > 1e-4


def test_targets_are_the_72_projections():
    shapes = jax.eval_shape(lambda k: M.init_params(k, ROBERTA),
                            jax.random.PRNGKey(0))
    train, _ = split_trainable(shapes, galore_target_fn(ROBERTA))
    flat = jax.tree_util.tree_flatten_with_path(train)[0]
    names = sorted("/".join(str(getattr(q, "key", q)) for q in p[-2:])
                   for p, _ in flat)
    assert names == ["attn/wk", "attn/wo", "attn/wq", "attn/wv",
                     "mlp/w_down", "mlp/w_up"]
    assert sum(x.shape[0] for _, x in flat) == 72


def test_logits_and_loss_match_the_fp32_reference():
    """Both sides are fp32 and read the same weights; they differ only in
    the order of their sums (and the program's fp32 default matmul against
    the reference's ``highest``, the same on the CPU), so a few ulps of the
    logits' size bound the gap: 1e-5 relative to the largest logit, 1e-5 on
    the loss."""
    params = random_weights(SMALL)
    tokens, labels = _inputs(SMALL)
    got, _ = M.forward(params, SMALL, tokens)
    want = ref_forward(params, SMALL, tokens)
    assert got.shape == (3, SMALL.n_classes)
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)
    loss = M.loss_fn(params, SMALL, {"tokens": tokens, "labels": labels})
    np.testing.assert_allclose(loss, ref_loss(params, SMALL, tokens, labels),
                               atol=1e-5, rtol=1e-5)


def test_gradients_match_the_fp32_reference():
    """Every leaf's gradient, within 1e-5 of the largest gradient entry
    (fp32 sums in another order; some leaves' true gradient is zero, e.g.
    the key bias under the softmax's shift invariance)."""
    params = random_weights(SMALL)
    tokens, labels = _inputs(SMALL)
    g = jax.grad(lambda p: M.loss_fn(p, SMALL, {"tokens": tokens,
                                                "labels": labels}))(params)
    r = jax.grad(lambda p: ref_loss(p, SMALL, tokens, labels))(params)
    scale = max(float(jnp.max(jnp.abs(x)))
                for x in jax.tree_util.tree_leaves(r))
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(r)):
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, rtol=0)


def test_bidirectional_last_token_reaches_the_s_row():
    """The head reads the <s> row, position 0. Under a causal mask that row
    sees only itself, so changing the last token could not move it."""
    params = random_weights(SMALL)
    tokens, _ = _inputs(SMALL)
    other = tokens.at[:, -1].set((tokens[:, -1] + 1) % SMALL.vocab_size)
    a, _ = M.forward(params, SMALL, tokens)
    b, _ = M.forward(params, SMALL, other)
    assert float(jnp.min(jnp.max(jnp.abs(a - b), axis=-1))) > 1e-4


def test_post_ln_normalizes_each_block_output():
    """With both sublayers' outputs zeroed (wo, bo, w_down, b_down = 0), a
    post-LN block still normalizes: the <s> row reaches the head as
    norm2(norm1(e)), e the normalized embedding. Pre-LN would pass e
    through unchanged."""
    cfg = dataclasses.replace(SMALL, n_layers=1)
    params = random_weights(cfg)
    blk = params["blocks"][0]
    for g, n in (("attn", "wo"), ("attn", "bo"), ("mlp", "w_down"),
                 ("mlp", "b_down")):
        blk[g][n] = jnp.zeros_like(blk[g][n])
    tokens, _ = _inputs(cfg)
    e = params["embed"]
    emb = _ln(e["w"][tokens[:, 0]] + e["pos"][2] + e["type"][0], e["norm"])
    n1 = {k: v[0] for k, v in blk["norm1"].items()}
    n2 = {k: v[0] for k, v in blk["norm2"].items()}
    hp = params["cls_head"]

    def head(h0):
        y = jnp.tanh(_mm("bd,de->be", h0, hp["dense"]["w"])
                     + hp["dense"]["b"])
        return _mm("bd,dc->bc", y, hp["out_proj"]["w"]) + hp["out_proj"]["b"]

    got, _ = M.forward(params, cfg, tokens)
    np.testing.assert_allclose(got, head(_ln(_ln(emb, n1), n2)), atol=1e-5)
    assert float(jnp.max(jnp.abs(got - head(emb)))) > 1e-3


def test_encoder_has_no_decode():
    with pytest.raises(ValueError, match="bidirectional encoder"):
        M.init_decode_state(SMALL, 2, 16)


def test_serving_entry_refuses_an_encoder():
    with pytest.raises(ValueError, match="roberta-base is a bidirectional "
                                         "encoder.*no decode"):
        serve.main(["--arch", "roberta-base", "--smoke"])

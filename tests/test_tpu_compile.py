"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler ships with the installed jaxlib and compiles for a chip that
is described, not attached, so these tests catch what interpret mode cannot:
ops the kernel compiler cannot lower, block shapes it refuses, and more VMEM
than a kernel may use. Shapes are the qwen1.5-0.5b widths the round and the
server run at (d=1024, d_ff=2816, rank 8, bf16 activations), and the
roberta-base widths of the encoder round (d=768, d_ff=3072). Nothing runs;
a compile that passes says nothing about results or times.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_eigh import jacobi_eigh
from repro.kernels.flash_attention import flash_attention
from repro.kernels.galore_adamw import galore_precond_step
from repro.kernels.lowrank_linear import lowrank_linear, lowrank_linear_batched

D, FF, R = 1024, 2816, 8
TOKENS = 2 * 256          # one client's local batch: 2 sequences x 256
G = 16                    # adapters resident in the serving table


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, bf16 = jnp.float32, jnp.bfloat16

# (name, shape of g) — right: m >= n (w_down), left: m < n (w_gate / w_up).
# The leading 24 is the stacked layer dim the bucketed step vmaps over.
_GALORE = {
    "right": ((24, FF, D), (24, D, R), (24, FF, R), True),
    "left": ((24, D, FF), (24, D, R), (24, R, FF), True),
    "projected": ((24, FF, D), (24, D, R), (24, FF, R), False),
}


@pytest.mark.parametrize("case", sorted(_GALORE))
def test_galore_precond_step_compiles(one_chip, case):
    g, basis, mv, project_back = _GALORE[case]

    def step(g, basis, m, v, count):
        return galore_precond_step(
            g, basis, m, v, count, project_back=project_back)

    _compile(step, one_chip, (g, f32), (basis, f32), (mv, f32), (mv, f32),
             ((), f32))


CLIENTS, CELL_TOKENS = 4, 2 * 512   # the round's cohort chunk, vmapped
# roberta-base cell: a chunk of 16 clients, 8 x 128 tokens each
ENC_D, ENC_FF, ENC_CLIENTS, ENC_TOKENS = 768, 3072, 16, 8 * 128

# (x, w, basis, rt) of one call; the *-cell cases are the fed-round cell's
# calls: CLIENTS clients vmapped over one shared base, CELL_TOKENS each.
_LOWRANK = {
    "right": ((TOKENS, FF), (FF, D), (D, R), (FF, R)),
    "left": ((TOKENS, D), (D, FF), (D, R), (R, FF)),
    "wq-cell": ((CLIENTS, CELL_TOKENS, D), (D, D), (CLIENTS, D, R),
                (CLIENTS, D, R)),
    "w_gate-cell": ((CLIENTS, CELL_TOKENS, D), (D, FF), (CLIENTS, D, R),
                    (CLIENTS, R, FF)),
    "w_down-cell": ((CLIENTS, CELL_TOKENS, FF), (FF, D), (CLIENTS, D, R),
                    (CLIENTS, FF, R)),
    "encoder-wq-cell": ((ENC_CLIENTS, ENC_TOKENS, ENC_D), (ENC_D, ENC_D),
                        (ENC_CLIENTS, ENC_D, R), (ENC_CLIENTS, ENC_D, R)),
    "encoder-w_up-cell": ((ENC_CLIENTS, ENC_TOKENS, ENC_D), (ENC_D, ENC_FF),
                          (ENC_CLIENTS, ENC_D, R), (ENC_CLIENTS, R, ENC_FF)),
    "encoder-w_down-cell": ((ENC_CLIENTS, ENC_TOKENS, ENC_FF),
                            (ENC_FF, ENC_D), (ENC_CLIENTS, ENC_D, R),
                            (ENC_CLIENTS, ENC_FF, R)),
}


@pytest.mark.parametrize("case", sorted(_LOWRANK))
def test_lowrank_linear_compiles(one_chip, case):
    x, w, basis, rt = _LOWRANK[case]
    if len(x) == 2:
        _compile(lowrank_linear, one_chip, (x, bf16), (w, bf16),
                 (basis, f32), (rt, f32), ((), f32))
        return

    def clients(x, w, basis, rt, scale):
        return jax.vmap(lowrank_linear, in_axes=(0, None, 0, 0, 0))(
            x, w, basis, rt, scale)

    n = x[0]
    text = _compile(clients, one_chip, (x, bf16), (w, bf16), (basis, f32),
                    (rt, f32), ((n,), f32)).as_text()
    # the operands the roofline reader counts: (scale, x, w, basis, rt)
    call = next(l for l in text.splitlines() if "tpu_custom_call" in l)
    operands = call.split("operand_layout_constraints={", 1)[1]
    want = [f"f32[{n},1,1]", f"bf16[{','.join(map(str, x))}]",
            f"bf16[{','.join(map(str, w))}]",
            f"f32[{','.join(map(str, basis))}]",
            f"f32[{','.join(map(str, rt))}]"]
    assert [o.split("{")[0] for o in operands.split("}, ")[:5]] == want


@pytest.mark.parametrize("x_shape", [(G, D), (G, 128, D)],
                         ids=["decode", "prefill"])
def test_lowrank_linear_batched_compiles(one_chip, x_shape):
    def apply(x, w, bases, rts, scales, ids):
        return lowrank_linear_batched(x, w, bases, rts, scales, ids)

    _compile(apply, one_chip, (x_shape, bf16), ((D, FF), bf16),
             ((G, D, R), f32), ((G, R, FF), f32), ((G,), f32),
             ((G,), jnp.int32))


@pytest.mark.parametrize("n", [8, 32])
def test_jacobi_eigh_compiles(one_chip, n):
    _compile(jacobi_eigh, one_chip, ((64, n, n), f32))


# the qwen cell's attention: a chunk of CLIENTS clients vmapped, each a
# (2, 512) batch of 16 heads of 64
ATTN = (CLIENTS, 2, 512, 16, 64)


def _flash_cell(q, k, v):
    return jax.vmap(lambda q, k, v: flash_attention(q, k, v))(q, k, v)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles(one_chip, direction):
    if direction == "forward":
        fn = _flash_cell
    else:
        def fn(q, k, v):
            o, vjp = jax.vjp(_flash_cell, q, k, v)
            return vjp(o)
    text = _compile(fn, one_chip, (ATTN, bf16), (ATTN, bf16),
                    (ATTN, bf16)).as_text()
    kinds = {"forward": ["fwd"], "backward": ["fwd", "dq", "dkv"]}[direction]
    for kind in kinds:
        assert f"%flash_attention_{kind}_causal" in text


def test_flash_route_keeps_the_attention_scope(one_chip, monkeypatch):
    """``gqa_forward`` on the kernel route, differentiated: the forward and
    both backward kernels carry ``model.attention`` in their ``op_name``,
    so ``attention_ms.round`` still reads all of attention."""
    import re

    from repro.kernels import ops
    from repro.models import attention
    monkeypatch.setattr(ops, "use_kernels", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    c, b, l, h, d = ATTN
    p = jax.eval_shape(lambda: attention.gqa_init(
        jax.random.PRNGKey(0), h * d, h, h, d, qkv_bias=True, dtype=bf16))

    def loss(p, x):
        out, _ = attention.gqa_forward(p, x, jnp.arange(l), n_heads=h,
                                       n_kv=h, head_dim=d, attn_chunk=4096)
        return jnp.sum(out.astype(f32) ** 2)

    args = [jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), p),
        jax.ShapeDtypeStruct((b, l, h * d), bf16, sharding=one_chip)]
    text = jax.jit(jax.value_and_grad(loss)).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "flash_attention_" in line]
    names = {re.search(r"%(flash_attention_\w+?)\.\d+ = ", c).group(1):
             re.search(r'op_name="([^"]*)"', c).group(1) for c in calls}
    assert sorted(names) == ["flash_attention_dkv_causal",
                             "flash_attention_dq_causal",
                             "flash_attention_fwd_causal"]
    for op_name in names.values():
        assert "model.attention" in op_name

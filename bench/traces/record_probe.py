"""Record ``probe-v5e.xplane.pb`` on one chip (run from the checkout root):

    python3 bench/traces/record_probe.py <out_dir>

Three ``bench.step`` spans under one ``bench.window`` span, each around the
fused ``lowrank_linear`` apply, the per-row ``lowrank_linear_batched``
apply, one projected ``galore_precond_step`` and a bf16 matmul, with 10 ms
of host sleep between steps.
"""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from repro.kernels import ops  # noqa: E402

D, FF, R, G = 1024, 2816, 8, 16


def main(out_dir: str) -> None:
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (1024, D), jnp.bfloat16)
    w = (jax.random.normal(k, (D, FF)) * 0.02).astype(jnp.bfloat16)
    basis = jnp.linalg.qr(jax.random.normal(k, (D, R)))[0]
    rt = 0.01 * jax.random.normal(k, (R, FF))
    bases = jax.random.normal(k, (G, D, R)) / 32
    rts = 0.01 * jax.random.normal(k, (G, R, FF))
    ids = jnp.arange(32, dtype=jnp.int32) % G
    xb = jax.random.normal(k, (32, D), jnp.bfloat16)
    g = jax.random.normal(k, (FF, D))
    m0 = jnp.zeros((FF, R))
    f = jax.jit(lambda x, w: ops.lowrank_linear(x, w, basis, rt, 1.0,
                                                side="left"))
    fb = jax.jit(lambda x, w: ops.lowrank_linear_batched(
        x, w, bases, rts, jnp.ones((G,)), ids, side="left"))
    fp = jax.jit(lambda g: ops.galore_precond_step(
        g, basis, m0, m0, 1.0, side="right", project_back=False))
    mm = jax.jit(lambda a, b: a @ b)

    def step():
        jax.block_until_ready((f(x, w), fb(xb, w), fp(g), mm(w.T, w)))

    step()
    step()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step()
            time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])

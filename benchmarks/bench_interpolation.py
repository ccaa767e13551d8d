"""Fig 3c analogue: linear-interpolation loss barrier between client models.

Two clients train from a shared init on disjoint (non-IID) halves of the
task with (a) full fine-tuning, (b) LoRA, (c) GaLore. We evaluate the global
loss along θ(t) = (1-t)·θ_A + t·θ_B and report two connectivity metrics:

    barrier  = max_t L(θ(t)) − max(L(θ_A), L(θ_B))        (≥ 0)
    midpoint = L(θ(0.5)) − ½(L(θ_A) + L(θ_B))             (sign-sensitive)

At smoke scale the hard barrier is often exactly 0 (both endpoints stay in
one convex region after ≤60 local steps), so the sign-sensitive midpoint
excess is the informative statistic. Paper claim: FFT and GaLore interpolate
better than LoRA.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_variant
from repro.core.fed import FedConfig, FedEngine
from repro.data import FederatedBatcher, seq_classification
from repro.launch.steps import galore_target_fn
from repro.models import model as M
from .common import emit

METHOD_OF = {"fft": "fedavg_full", "galore": "fedgalore_minus",
             "lora": "fedit"}


def client_models(kind: str, rounds=10, seed=0):
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    task = seq_classification(512, 4, 16, cfg.vocab_size, seed=seed)
    batcher = FederatedBatcher(task, 2, 8, alpha=0.05, seed=seed)

    def loss(p, b):
        return M.loss_fn(p, cfg, b)

    eng = FedEngine(FedConfig(method=METHOD_OF[kind], rank=4, lr=2e-2,
                              local_steps=6, seed=seed),
                    loss, params, target_fn=galore_target_fn(cfg))
    # one broadcast, then LOCAL-ONLY training (no aggregation): capture the
    # two client endpoints by running a round and reading stacked trainables.
    batches = {k: jnp.asarray(v) for k, v in
               batcher.round_batches(6 * rounds).items()}
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (2,) + x.shape), eng.global_trainable)
    opt = eng._stack_opt_state(
        eng._init_state0(0, None, eng.global_trainable), 2)
    local = jax.vmap(eng._local_train_one,
                     in_axes=(0, eng._opt_axes, 0, None),
                     out_axes=(0, eng._opt_axes, 0))
    out_tr, _, _ = jax.jit(local)(stacked, opt, batches, eng.frozen)

    def client_params(i):
        tr = jax.tree_util.tree_map(lambda x: x[i], out_tr)
        if eng.spec.trainable in ("dense", "galore"):
            from repro.core.fed import merge_dense
            return merge_dense(eng.frozen, tr)
        from repro.core.fed import merge_lora
        return merge_lora(eng.frozen, tr, eng.cfg.lora_scale)

    eval_b = {k: jnp.asarray(v) for k, v in batcher.eval_batch(256).items()}
    return cfg, client_params(0), client_params(1), eval_b


def barrier(kind: str, n_pts=9, seed=0):
    cfg, pa, pb, eval_b = client_models(kind, seed=seed)

    def loss_at(t):
        p = jax.tree_util.tree_map(
            lambda a, b: (1 - t) * a.astype(jnp.float32)
            + t * b.astype(jnp.float32), pa, pb)
        return float(M.loss_fn(p, cfg, eval_b))

    ts = np.linspace(0, 1, n_pts)
    path = [loss_at(float(t)) for t in ts]
    hard = max(path) - max(path[0], path[-1])
    mid = path[n_pts // 2] - 0.5 * (path[0] + path[-1])
    return hard, mid, path


def main(seeds=(0, 1)):
    rows = {}
    for kind in ("fft", "galore", "lora"):
        t0 = time.perf_counter()
        res = [barrier(kind, seed=s) for s in seeds]
        dt = time.perf_counter() - t0
        rows[kind] = {"barrier": float(np.mean([r[0] for r in res])),
                      "midpoint_excess": float(np.mean([r[1] for r in res]))}
        emit(f"interpolation/{kind}", dt / len(seeds) * 1e6,
             f"barrier={rows[kind]['barrier']:.4f};"
             f"midpoint={rows[kind]['midpoint_excess']:+.4f}")
    with open("bench_interpolation.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    main()

"""Federated training launcher.

Runs FedGaLore (or any registered baseline) on a synthetic task with the
Dirichlet(α) protocol — the host-scale end-to-end driver. On real hardware
the same step functions lower onto the production mesh (see dryrun.py); here
the mesh is whatever devices exist.

  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen1.5-0.5b --smoke --method fedgalore --rounds 20 \
      --clients 8 --participate 4 --alpha 0.5
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, smoke_variant
from ..core.fed import FedConfig, FedEngine, METHODS
from ..data import FederatedBatcher, seq_classification
from ..models import model as model_lib
from .cache import use_compile_cache
from .steps import galore_target_fn

EVAL_ROWS = 8   # rows per evaluation program: bounds its full-vocab logits


@functools.lru_cache(maxsize=None)
def _eval_fn(cfg):
    """(loss, labelled positions, last-position hits) for one row chunk."""
    def run(params, batch):
        logits, _ = model_lib.forward(params, cfg, batch["tokens"],
                                      batch.get("embeds"))
        hits = jnp.sum(jnp.argmax(logits[:, -1], -1) == batch["labels"][:, -1])
        return (model_lib.loss_fn(params, cfg, batch),
                jnp.sum(batch["labels"] >= 0), hits)
    return jax.jit(run)


def evaluate(params, cfg, batch):
    """Validation loss and last-position accuracy over ``batch``, run in
    chunks of ``EVAL_ROWS`` rows: a whole batch's fp32 logits and their
    log-softmax would not fit a chip at a 150k vocabulary. The loss is the
    labelled-position-weighted mean of the chunk losses."""
    n = len(batch["tokens"])
    loss_sum = labelled = hits = 0.0
    for i in range(0, n, EVAL_ROWS):
        chunk = {k: v[i:i + EVAL_ROWS] for k, v in batch.items()}
        loss, count, hit = _eval_fn(cfg)(params, chunk)
        loss_sum += float(loss) * int(count)
        labelled += int(count)
        hits += int(hit)
    return loss_sum / max(labelled, 1), hits / n


def main(argv=None):
    """Run the federated rounds and print one JSON line per round. Returns
    ``{"history", "engine", "last_batches"}``: the per-round rows, the
    engine with the final global state, and the last round's batches."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke variant (CPU-scale)")
    ap.add_argument("--method", default="fedgalore", choices=list(METHODS))
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--participate", type=int, default=0,
                    help="clients per round (0 = all)")
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=None,
                    help="Dirichlet alpha (None = IID)")
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--examples", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init_params(key, cfg)

    task = seq_classification(args.examples, args.classes, args.seq,
                              cfg.vocab_size, seed=args.seed)
    batcher = FederatedBatcher(task, args.clients, args.batch,
                               alpha=args.alpha, seed=args.seed)

    def loss(p, batch):
        return model_lib.loss_fn(p, cfg, batch)

    fed_cfg = FedConfig(method=args.method, rank=args.rank, lr=args.lr,
                        local_steps=args.local_steps, rounds=args.rounds,
                        seed=args.seed)
    engine = FedEngine(fed_cfg, loss, params,
                       target_fn=galore_target_fn(cfg))

    eval_batch = {k: jnp.asarray(v) for k, v in
                  batcher.eval_batch(min(256, args.examples)).items()}

    history = []
    for rnd in range(args.rounds):
        t0 = time.time()
        clients = (batcher.sample_clients(args.participate)
                   if args.participate else None)
        batches = batcher.round_batches(args.local_steps, clients)
        batches = {k: jnp.asarray(v) for k, v in batches.items()}
        metrics = engine.run_round(batches)
        gp = engine.global_params()
        val, acc = evaluate(gp, cfg, eval_batch)
        row = {"round": rnd, "local_loss": metrics["mean_final_loss"],
               "val_loss": val, "val_acc": acc,
               "sec": round(time.time() - t0, 2)}
        history.append(row)
        print(json.dumps(row), flush=True)
        if args.ckpt_dir:
            from ..checkpoint import save
            save(args.ckpt_dir, rnd, gp)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(history, f, indent=1)
    return {"history": history, "engine": engine, "last_batches": batches}


if __name__ == "__main__":
    main()

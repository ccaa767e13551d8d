"""End-to-end driver: federated fine-tuning of roberta-base, the published
bidirectional encoder (12L, d=768, ~125M parameters; the paper's NLU
setting), as a sequence classifier read at the ``<s>`` row, for a few
hundred local steps total, comparing FedGaLore against a federated-LoRA
baseline under non-IID data.

    PYTHONPATH=src python examples/federated_finetune_100m.py \
        [--rounds 50] [--method fedgalore] [--alpha 0.5]

Reduce --rounds for a quick run; 50 rounds × 4 local steps = 200 optimizer
steps per client stream (the "few hundred steps" end-to-end budget).
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.fed import FedConfig, FedEngine
from repro.data import FederatedBatcher, seq_classification
from repro.launch.steps import galore_target_fn
from repro.models import model as M


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--method", default="fedgalore")
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args(argv)

    n_classes = 8
    cfg = dataclasses.replace(get_config("roberta-base"), n_classes=n_classes)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} params={n_params/1e6:.0f}M")

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    task = seq_classification(4096, n_classes, args.seq, cfg.vocab_size)
    # The encoder reads <s> (id 0) at the first position and predicts one
    # class id per sequence.
    task = dataclasses.replace(
        task, tokens=np.concatenate(
            [np.zeros_like(task.tokens[:, :1]), task.tokens[:, 1:]], axis=1),
        labels=task.class_ids.astype(np.int32))
    clients = FederatedBatcher(task, args.clients, args.batch,
                               alpha=args.alpha)

    engine = FedEngine(
        FedConfig(method=args.method, rank=8, lr=1e-4,
                  local_steps=args.local_steps),
        loss_fn=lambda p, b: M.loss_fn(p, cfg, b),
        params=params, target_fn=galore_target_fn(cfg))

    eval_b = clients.eval_batch(128)
    t_start = time.time()
    for rnd in range(args.rounds):
        t0 = time.time()
        batches = {k: jnp.asarray(v)
                   for k, v in clients.round_batches(args.local_steps).items()}
        metrics = engine.run_round(batches)
        if rnd % 5 == 0 or rnd == args.rounds - 1:
            gp = engine.global_params()
            logits, _ = M.forward(gp, cfg, jnp.asarray(eval_b["tokens"]))
            acc = float((np.asarray(logits).argmax(-1)
                         == eval_b["labels"]).mean())
            val = float(M.loss_fn(gp, cfg, {k: jnp.asarray(v)
                                            for k, v in eval_b.items()}))
            print(json.dumps({"round": rnd,
                              "local_loss": round(metrics["mean_final_loss"], 4),
                              "val_loss": round(val, 4), "val_acc": acc,
                              "round_sec": round(time.time() - t0, 1)}),
                  flush=True)
    print(f"total: {args.rounds} rounds, "
          f"{args.rounds * args.local_steps} local steps/client, "
          f"{time.time() - t_start:.0f}s")


if __name__ == "__main__":
    main()

"""Driver kind ``round``: the federated round through ``FedEngine.run_round``,
the fused one-program-per-round path, on one chip.

Set-up makes the weights from the seed, builds one engine, and drives it
through the cell's first rounds (``setup_rounds``: round 0 takes the
data-driven basis refresh, round 1 the branch every window round takes) on
the same feed the window uses. Those rounds compile the round program and
are what the reference follows. The window's batches are then made and put
on the device, so that the window times the program alone. The window runs
whole rounds until ``--seconds`` have passed; ``round_s`` is the window's
time over its rounds. After the window the program's state is freed and
the plain reference (``bench/reference/galore_round.py``) runs the same
first rounds from the same seed.

Compared, each against the limit in the cell file:

- ``loss_gap``: the widest relative gap between a local-step loss of the
  first rounds and the reference's;
- ``grad_gap``: round 1's global change (the pseudo-gradient the server
  applies, in the first round of the window's branch), by the worst
  layer-matrix: the gap between the program's Frobenius norm and the
  reference's, over the larger of the reference's norm and the median
  matrix's;
- ``change_gap``: the same for the global change over all the first rounds.

Matrices whose reference change is under a thousandth of the median's are
left out of both norms.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict

import numpy as np

import common

GRAD_ROUND = 1


def target_norms(tree, base) -> Dict[str, np.ndarray]:
    """Per-layer Frobenius norm of tree - base for each stacked target,
    keyed ``attn/wq``-style."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(
                (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2,
                axis=(-2, -1))), a, b)

    out = {}
    flat = jax.tree_util.tree_flatten_with_path(norms(tree, base))[0]
    for path, v in flat:
        keys = [str(getattr(q, "key", getattr(q, "idx", q))) for q in path]
        out["/".join(keys[-2:])] = np.asarray(v)
    return out


def norm_gap(prog: Dict, ref: Dict, keep=None) -> float:
    """Worst-matrix gap |‖p‖ - ‖r‖| / max(‖r‖, median ‖r‖)."""
    r_all = np.concatenate([ref[k] for k in sorted(ref)])
    p_all = np.concatenate([prog[k] for k in sorted(ref)])
    keep = np.ones_like(r_all, bool) if keep is None else keep
    med = float(np.median(r_all[keep]))
    den = np.maximum(r_all, med)
    return float(np.max(np.abs(p_all - r_all)[keep] / den[keep]))


def keep_mask(ref: Dict) -> np.ndarray:
    r_all = np.concatenate([ref[k] for k in sorted(ref)])
    return r_all >= 1e-3 * float(np.median(r_all))


def fed_settings(fed: Dict) -> Dict:
    """The round's settings. The server's broadcast seed is the cell's, not
    the run's: the program bakes it into the round program, so a seed per
    run would compile a new program in every run's set-up."""
    return {"rank": fed["rank"], "lr": fed["lr"],
            "local_steps": fed["local_steps"], "clip_norm": 1.0,
            "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "seed": fed["broadcast_seed"]}


def traffic_of(cell, conf, seed):
    from traffic.federated import DirichletSeqClassification
    fed = cell["fed"]
    return DirichletSeqClassification(
        seed=seed, vocab=conf["arch"]["vocab_size"], clients=fed["clients"],
        local_steps=fed["local_steps"], batch=fed["batch"],
        seq_len=fed["seq_len"], classes=fed["classes"], alpha=fed["alpha"])


def device_round(traffic, index: int):
    import jax.numpy as jnp
    return {n: jnp.asarray(v) for n, v in traffic.round(index).items()}


def build_engine(arch, params, fed: Dict):
    common.program_path()
    from repro.core.fed import FedConfig, FedEngine
    from repro.launch.steps import galore_target_fn
    from repro.models import model as model_lib

    s = fed_settings(fed)
    cfg = FedConfig(method=fed["method"], rank=s["rank"], lr=s["lr"],
                    local_steps=s["local_steps"], seed=s["seed"],
                    client_chunk=fed.get("client_chunk"))
    return FedEngine(cfg, lambda p, b: model_lib.loss_fn(p, arch, b),
                     params, target_fn=galore_target_fn(arch))


def program_first_rounds(ctx, arch, traffic, n_rounds):
    """Weights, engine and the first rounds; returns the engine, the
    readings compared later, and the time of the last of those rounds."""
    fed = ctx.cell["fed"]
    params = common.make_weights(arch, ctx.seed)
    engine = build_engine(arch, params, fed)
    w0 = engine.global_trainable
    losses, before, norm_grad, last_s = [], w0, None, None
    for k in range(n_rounds):
        t0 = time.perf_counter()
        out = engine.run_round(device_round(traffic, k))
        losses.append(np.asarray(out["local_loss"], np.float64))
        if k == GRAD_ROUND:
            norm_grad = target_norms(engine.global_trainable, before)
        before = engine.global_trainable
        last_s = time.perf_counter() - t0
        ctx.log(f"set-up round {k}: {last_s:.3f} s, "
                f"mean final local loss {out['mean_final_loss']:.6f}")
    readings = {"losses": np.stack(losses), "norm_grad": norm_grad,
                "norm_last": target_norms(engine.global_trainable, w0)}
    return engine, readings, last_s


def reference_readings(ctx, traffic, n_rounds, mode="f32"):
    from reference.galore_round import reference_rounds
    arch = common.arch_config(ctx.conf)
    params = common.make_weights(arch, ctx.seed)
    rounds = [traffic.round(k) for k in range(n_rounds)]
    return reference_rounds(params, ctx.conf["arch"],
                            fed_settings(ctx.cell["fed"]), rounds,
                            grad_round=GRAD_ROUND, mode=mode)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    lp, lr = prog["losses"], ref["losses"]
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": norm_gap(prog["norm_grad"], ref["norm_grad"],
                             keep_mask(ref["norm_grad"])),
        "change_gap": norm_gap(prog["norm_last"], ref["norm_last"],
                               keep_mask(ref["norm_last"])),
    }


def run(ctx) -> Dict:
    cell = ctx.cell
    fed = cell["fed"]
    arch = common.arch_config(ctx.conf)
    traffic = traffic_of(cell, ctx.conf, ctx.seed)
    n_first = fed["setup_rounds"]
    engine, prog, last_s = program_first_rounds(ctx, arch, traffic, n_first)
    # The window's feed, made before it opens: twice the rounds the last
    # set-up round's pace would fit, and four more.
    n_window = math.ceil(2 * ctx.seconds / last_s) + 4
    feed = [device_round(traffic, n_first + i) for i in range(n_window)]
    ctx.setup_done()

    rounds, failed = 0, 0
    t_start = time.perf_counter()
    trace_left = fed["trace_rounds"] if ctx.trace else 0
    if trace_left:
        ctx.trace_start()
    while True:
        if rounds == len(feed):
            with ctx.span("bench.batch"):
                feed.append(device_round(traffic, n_first + rounds))
        with ctx.span("bench.round"):
            out = engine.run_round(feed[rounds])
        if not np.isfinite(out["mean_final_loss"]):
            failed += 1
        rounds += 1
        if trace_left:
            trace_left -= 1
            if trace_left == 0:
                ctx.trace_stop(work={"rounds": rounds})
        if time.perf_counter() - t_start >= ctx.seconds and not trace_left:
            break
    elapsed = time.perf_counter() - t_start
    ctx.log(f"window: {rounds} rounds in {elapsed:.4f} s "
            f"({len(feed) - n_window} batches made inside it)")
    ctx.read_memory()

    del engine, feed, out
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_readings(ctx, traffic, n_first)
    checks = compare(prog, ref)
    ctx.log(f"reference: {time.perf_counter() - t0:.3f} s")
    return {"e2e": {"round_s": elapsed / rounds},
            "attempted": rounds, "failed": failed, "checks": checks}


def readings(ctx, faults=()) -> Dict:
    """Compared numbers against one reference, with no window: the
    program's, the float8 control's (the reference put in the program's
    place), and the program's with each fault in ``faults`` planted."""
    import faults as planted
    arch = common.arch_config(ctx.conf)
    traffic = traffic_of(ctx.cell, ctx.conf, ctx.seed)
    n_first = ctx.cell["fed"]["setup_rounds"]

    def program():
        engine, prog, _ = program_first_rounds(ctx, arch, traffic, n_first)
        del engine
        gc.collect()
        return prog

    progs = {"program": program()}
    for name in faults:
        with planted.planted(name):
            progs[name] = program()
    ref = reference_readings(ctx, traffic, n_first)
    progs["control"] = reference_readings(ctx, traffic, n_first, mode="fp8")
    return {k: compare(v, ref) for k, v in progs.items()}

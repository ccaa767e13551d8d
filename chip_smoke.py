"""Smoke run of both end-to-end paths on a TPU, at qwen1.5-0.5b full width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded round only

One chip runs, in order:

1. a device check that fails unless JAX's first device is a TPU;
2. the main-path Pallas kernels against their jnp references
   (``repro/kernels/ref.py``, ``jnp.linalg.eigh``) at the model's widths;
3. two federated rounds through ``repro.launch.train.main`` (fedgalore,
   4 clients, 2 local steps of 2 x 256 tokens, rank 8);
4. multi-tenant serving through ``repro.launch.serve.main``: one fused-scan
   batch and one continuous-batching stream, 8 adapters.

``--chips 4`` runs one ``ShardedFederation`` fedgalore round on a
(data=4, model=1) mesh and the same round with the same inputs on a
one-device mesh, and compares them.

The model is the published configuration (24 layers, d=1024, vocab 151936,
bf16) with random weights made from seed 0. Every phase runs in this one
process, which owns the chips. A failed check raises, so the script exits
non-zero and never prints its result line; on success the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = ["--arch", "qwen1.5-0.5b"]
D, FF, R = 1024, 2816, 8          # qwen1.5-0.5b d_model, d_ff; GaLore rank
G = 16                            # adapters in the kernel's table


def check(name: str, value: float, limit: float) -> None:
    """Print ``value`` beside its limit; raise unless value <= limit (NaN
    fails)."""
    print(f"{name}: {value:.3e} (limit {limit:.3e})", flush=True)
    if not value <= limit:
        raise AssertionError(f"{name} = {value!r} exceeds {limit!r}")


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is a {dev['platform']} "
                         "device, and this check runs only on a TPU")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU devices, "
                         f"JAX reports {dev['count']}")
    return dev


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


# ------------------------------------------------------------- kernels -----

def kernel_phase() -> None:
    """Each main-path kernel at the model's widths against its reference,
    within the tolerance its CPU test uses for those dtypes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)

    def highest():
        return jax.default_matmul_precision("highest")

    def maxerr(got, want):
        return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))

    # GaLore preconditioning step (tests/test_kernels.py: 1e-5)
    for i, (name, (m, n), side, back) in enumerate((
            ("right", (FF, D), "right", True),
            ("left", (D, FF), "left", True),
            ("projected", (FF, D), "right", False))):
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        dim, mv = (n, (m, R)) if side == "right" else (m, (R, n))
        g = jax.random.normal(ks[0], (m, n))
        basis = jnp.linalg.qr(jax.random.normal(ks[1], (dim, R)))[0]
        mom = 0.1 * jax.random.normal(ks[2], mv)
        var = 0.01 * jnp.abs(jax.random.normal(ks[3], mv))
        got = ops.galore_precond_step(g, basis, mom, var, 5.0, side=side,
                                      project_back=back)
        with highest():
            want = ref.galore_precond_ref(g, basis, mom, var, count=5.0,
                                          side=side, project_back=back)
        check(f"kernel galore_precond_step[{name}] max|err|",
              maxerr(got, want), 1e-5)

    def tables(k, g, m, n, side):
        """Factor tables scaled as in tests/test_serve.py."""
        ks = jax.random.split(k, 3)
        bdim, rshape = (n, (g, m, R)) if side == "right" else (m, (g, R, n))
        bases = jax.random.normal(ks[0], (g, bdim, R)) / math.sqrt(bdim)
        rts = 0.1 * jax.random.normal(ks[1], rshape)
        scales = 1.0 + 0.1 * jax.random.normal(ks[2], (g,))
        return bases, rts, scales

    # The low-rank applies take the model's bf16 activations and base
    # weights (fp32 factors) and return bf16, so they are held to the bf16
    # tolerance of tests/test_serve.py (5e-2). An fp32 (2816, 1024) weight
    # block would not fit the kernel's 16 MiB of scoped VMEM.
    bf16 = jnp.bfloat16

    # lift-free low-rank apply, one client's 2 x 256 tokens
    for i, (side, (m, n)) in enumerate((("right", (FF, D)),
                                        ("left", (D, FF)))):
        k = jax.random.fold_in(key, 10 + i)
        x = jax.random.normal(k, (512, m), bf16)
        w = (jax.random.normal(jax.random.fold_in(k, 1), (m, n)) /
             math.sqrt(m)).astype(bf16)
        bases, rts, scales = tables(jax.random.fold_in(k, 2), 1, m, n, side)
        got = ops.lowrank_linear(x, w, bases[0], rts[0], scales[0],
                                 side=side)
        with highest():
            want = ref.lowrank_linear_ref(x, w, bases[0], rts[0], scales[0],
                                          side=side)
        check(f"kernel lowrank_linear[{side}] max|err|",
              maxerr([got.astype(jnp.float32)], [want.astype(jnp.float32)]),
              5e-2)

    # per-row adapters, G=16 tenants
    k = jax.random.fold_in(key, 20)
    w = (jax.random.normal(k, (D, FF)) / math.sqrt(D)).astype(bf16)
    bases, rts, scales = tables(jax.random.fold_in(k, 1), G, D, FF, "left")
    ids = jax.random.permutation(jax.random.fold_in(k, 2), G)
    for name, shape in (("decode", (G, D)), ("prefill", (G, 128, D))):
        x = jax.random.normal(jax.random.fold_in(k, 3), shape, bf16)
        got = ops.lowrank_linear_batched(x, w, bases, rts, scales, ids,
                                         side="left")
        with highest():
            want = ref.lowrank_linear_batched_ref(x, w, bases, rts, scales,
                                                  ids, side="left")
        check(f"kernel lowrank_linear_batched[{name}] max|err|",
              maxerr([got.astype(jnp.float32)], [want.astype(jnp.float32)]),
              5e-2)

    # batched eigensolver against XLA's eigh (tests/test_batched_eigh.py:
    # eigenvalues and reconstruction 5e-5 of the largest eigenvalue,
    # orthonormality 1e-4)
    for n in (8, 32):
        x = jax.random.normal(jax.random.fold_in(key, 30 + n), (64, n, n))
        with highest():
            a = jnp.einsum("bik,bjk->bij", x, x) / n
        lam, vec = ops.batched_small_eigh(a)
        with highest():
            lam_ref = jnp.linalg.eigh(a)[0]
            gram = jnp.einsum("bij,bik->bjk", vec, vec)
            rec = jnp.einsum("bik,bk,bjk->bij", vec, lam, vec)
        scale = float(jnp.max(jnp.abs(lam_ref)))
        check(f"kernel batched_small_eigh[64x{n}x{n}] eigenvalue max|err|",
              float(jnp.max(jnp.abs(lam - lam_ref))), 5e-5 * scale)
        check(f"kernel batched_small_eigh[64x{n}x{n}] orthonormality "
              "max|err|", float(jnp.max(jnp.abs(gram - jnp.eye(n)))), 1e-4)
        check(f"kernel batched_small_eigh[64x{n}x{n}] reconstruction "
              "max|err|", float(jnp.max(jnp.abs(rec - a))), 5e-5 * scale)


# ------------------------------------------------------ federated round ----

def round_phase(arch_args=ARCH) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch import train

    res = train.main(arch_args + [
        "--method", "fedgalore", "--clients", "4", "--local-steps", "2",
        "--batch", "2", "--seq", "256", "--rank", "8", "--rounds", "2",
        "--examples", "512"])
    history = res["history"]
    if len(history) != 2:
        raise AssertionError(f"expected 2 rounds, got {len(history)}")
    for row in history:
        for k in ("local_loss", "val_loss"):
            if not math.isfinite(row[k]):
                raise AssertionError(f"round {row['round']} {k} = {row[k]}")
    print("round losses finite: " + ", ".join(
        f"round {r['round']} local {r['local_loss']:.6f} val "
        f"{r['val_loss']:.6f}" for r in history), flush=True)

    engine = res["engine"]
    moved = jax.tree_util.tree_map(
        lambda a, b: jnp.max(jnp.abs(a.astype(jnp.float32) -
                                     b.astype(jnp.float32))),
        engine.global_params(), engine.base_params)
    moved = max(float(x) for x in jax.tree_util.tree_leaves(moved))
    print(f"global params moved: max|Δ| {moved:.6e}", flush=True)
    if not moved > 0.0:
        raise AssertionError("global params did not change over 2 rounds")
    print(f"round peak device memory: {peak_bytes(jax.devices()[0])} bytes",
          flush=True)

    hlo = engine.lower_round(res["last_batches"]).compile().as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"round program tpu_custom_call count: {kernels}", flush=True)
    if kernels == 0:
        raise AssertionError("no Pallas kernel in the compiled round")


# -------------------------------------------------------------- serving ----

def serve_phase(arch_args=ARCH, vocab: int = 151936) -> None:
    import jax
    from repro.launch import serve

    common = arch_args + ["--prompt-len", "128", "--new-tokens", "32",
                          "--adapters", "8", "--adapter-rank", "8"]
    for mode, extra, n_req in (("scan", ["--batch", "8"], 8),
                               ("continuous", ["--batch", "8",
                                               "--requests", "16"], 16)):
        out = serve.main(common + ["--mode", mode] + extra)["outputs"]
        if sorted(out) != list(range(n_req)):
            raise AssertionError(f"{mode}: served {sorted(out)}, "
                                 f"expected requests 0..{n_req - 1}")
        for rid, toks in out.items():
            if len(toks) != 32 or not all(0 <= t < vocab for t in toks):
                raise AssertionError(f"{mode}: request {rid} returned "
                                     f"{len(toks)} tokens {toks}")
        print(f"serve {mode}: {n_req} requests finished, 32 in-range tokens "
              "each", flush=True)
    print(f"serve peak device memory: {peak_bytes(jax.devices()[0])} bytes",
          flush=True)


# ------------------------------------------------------------ 4 chips ------

def sharded_phase(devices, cfg=None, seq: int = 256) -> None:
    """One fedgalore round on a (data=4, model=1) mesh over ``devices``
    and on a one-device mesh of ``devices[:1]``, same inputs. bf16
    tolerance: the losses within one bf16 ulp of the loss (2⁻⁸·|loss|),
    the global params within two bf16 ulps of the largest weight
    (2⁻⁷·max|w|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.data import FederatedBatcher, seq_classification
    from repro.fedsim.runtime import ShardedFederation
    from repro.launch.steps import TrainSpec

    cfg = cfg or get_config(ARCH[1])
    clients = len(devices)
    task = seq_classification(64, 8, seq, cfg.vocab_size, seed=0)
    batches = {k: jnp.asarray(v) for k, v in FederatedBatcher(
        task, clients, 2, seed=0).round_batches(2).items()}

    def one_round(devs):
        mesh = Mesh(np.asarray(devs).reshape(len(devs), 1),
                    ("data", "model"))
        fed = ShardedFederation(cfg, TrainSpec(rank=R, local_steps=2), mesh,
                                n_clients=clients, seed=0)
        losses = np.asarray(fed.run_round(batches)["losses"])
        params = [np.asarray(x, np.float32)
                  for x in jax.tree_util.tree_leaves(fed.global_trainable)]
        return losses, params

    loss_mesh, params_mesh = one_round(devices)
    for d in devices:
        print(f"device {d.id} peak memory after the {clients}-device round: "
              f"{peak_bytes(d)} bytes", flush=True)
    loss_one, params_one = one_round(devices[:1])
    if not np.all(np.isfinite(loss_mesh)):
        raise AssertionError(f"non-finite losses {loss_mesh}")
    print(f"sharded round losses: {loss_mesh.tolist()}", flush=True)
    check("sharded vs one-device loss max|Δ|",
          float(np.max(np.abs(loss_mesh - loss_one))),
          2.0 ** -8 * float(np.max(np.abs(loss_one))))
    check("sharded vs one-device global param max|Δ|",
          max(float(np.max(np.abs(a - b)))
              for a, b in zip(params_mesh, params_one)),
          2.0 ** -7 * max(float(np.max(np.abs(b))) for b in params_one))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded round against one device")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    dev = device_check(args.chips)
    if args.chips == 4:
        import jax
        sharded_phase(jax.devices()[:4])
    else:
        kernel_phase()
        round_phase()
        serve_phase()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape × mesh) combination this lowers and
compiles the corresponding step function against ShapeDtypeStruct inputs
(no allocation), then reports:

  * ``compiled.memory_analysis()``  — per-device bytes (proves it fits)
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline
  * collective bytes parsed from the post-SPMD HLO text (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single --out dryrun.json
  python -m repro.launch.dryrun --arch deepseek-v2-236b --shape train_4k --mesh multi
"""

import argparse
import json
import os
import re
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ASSIGNED_ARCHS, SHAPES, cache_len, get_config,
                       input_specs, shape_variant)
from ..sharding.rules import ShardingRules, path_of
from .mesh import make_production_mesh
from . import steps as steps_lib

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_OP_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer bytes of every collective op in post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        tuple_part, dtype, dims, op = m.groups()
        if tuple_part is not None:
            total = sum(_bytes_of(d, s)
                        for d, s in _SHAPE_RE.findall(tuple_part))
        else:
            total = _bytes_of(dtype, dims)
        out[op] += total
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _client_axes(mesh) -> tuple:
    return tuple(n for n in ("pod", "data") if n in mesh.shape)


def _stack_sds(tree, c: int):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((c,) + x.shape, x.dtype), tree)


def _stack_opt_sds(opt_state, c: int):
    """Client-stack an optimizer-state ShapeDtypeStruct tree in the runtime's
    layout (``galore.stack_opt_state``): per-client moments/bases gain the
    leading client dim; the GaLore count/seed stay unbatched scalars."""
    from ..core import galore as gal
    return gal.map_opt_layout(
        opt_state,
        batched=lambda x: jax.ShapeDtypeStruct((c,) + x.shape, x.dtype))


def _client_shardings(mesh, rules_tp, tree, batch_axes):
    """Client-stacked leaves: client dim over (pod,data); inner dims by the
    TP-only param rules."""
    def one(path, leaf):
        spec = rules_tp.param_spec(path_of(path), leaf.shape[1:])
        dims = list(spec) + [None] * (leaf.ndim - 1 - len(spec))
        return NamedSharding(mesh, P(batch_axes, *dims))
    return jax.tree_util.tree_map_with_path(one, tree)


def _client_opt_shardings(mesh, tree, batch_axes, model_axis="model"):
    """Client-stacked optimizer states (``_stack_opt_sds`` layout): per-client
    ≥2-D leaves put the client dim over (pod,data) and shard the largest
    trailing dim over model when divisible; the unbatched GaLore count/seed
    scalars — and any other sub-2-D leaf — replicate (P())."""
    msize = mesh.shape[model_axis]

    def one(leaf):
        if leaf.ndim >= 2 :
            dims = [None] * leaf.ndim
            dims[0] = batch_axes
            # pick the largest remaining dim divisible by the model axis
            cands = sorted(range(1, leaf.ndim),
                           key=lambda i: -leaf.shape[i])
            for i in cands:
                if leaf.shape[i] % msize == 0 and leaf.shape[i] >= msize:
                    dims[i] = model_axis
                    break
            return NamedSharding(mesh, P(*dims))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(one, tree)


def lower_combination(arch: str, shape_name: str, mesh,
                      train_spec: Optional[steps_lib.TrainSpec] = None,
                      donate: bool = True, unroll: bool = False,
                      depth_blocks: Optional[int] = None):
    """Returns the lowered (unverified) computation for one combination.

    ``unroll`` lowers straight-line HLO (accurate cost_analysis);
    ``depth_blocks`` truncates the model to that many repeating blocks —
    used with ``unroll`` for the 1-block/2-block cost extrapolation.
    """
    import dataclasses as _dc
    shape = SHAPES[shape_name]
    cfg = shape_variant(get_config(arch), shape)
    if unroll:
        cfg = _dc.replace(cfg, unroll_blocks=True, remat=False)
    if depth_blocks is not None:
        cfg = _dc.replace(cfg, n_layers=cfg.block_period() * depth_blocks)
    rules = ShardingRules(mesh, fsdp=True)
    rules_tp = ShardingRules(mesh, fsdp=False)
    batch_axes = _client_axes(mesh)
    spec = train_spec or steps_lib.TrainSpec()
    spec = _dc.replace(spec, client_axes=batch_axes)

    if shape.kind == "train":
        n_clients = 1
        for a in batch_axes:
            n_clients *= mesh.shape[a]
        per_client = max(shape.global_batch // n_clients, 1)
        abstract = jax.eval_shape(
            lambda: steps_lib.init_train_state(jax.random.PRNGKey(0), cfg, spec))
        trainable, frozen, opt_state = abstract
        trainable_c = _stack_sds(trainable, n_clients)
        opt_c = _stack_opt_sds(opt_state, n_clients)
        batch = input_specs(cfg, shape)
        n_text = batch["tokens"].shape[1]
        cbatch = {"tokens": jax.ShapeDtypeStruct((n_clients, per_client, n_text),
                                                 jnp.int32),
                  "labels": jax.ShapeDtypeStruct((n_clients, per_client, n_text),
                                                 jnp.int32)}
        if "embeds" in batch:
            e = batch["embeds"]
            cbatch["embeds"] = jax.ShapeDtypeStruct(
                (n_clients, per_client) + e.shape[1:], e.dtype)
        step = steps_lib.make_fed_local_step(cfg, spec, n_clients)
        in_shardings = (
            _client_shardings(mesh, rules_tp, trainable_c, batch_axes),
            rules.params_shardings(frozen),
            _client_opt_shardings(mesh, opt_c, batch_axes),
            jax.tree_util.tree_map(
                lambda x: NamedSharding(mesh, P(batch_axes,
                                                *([None] * (x.ndim - 1)))),
                cbatch),
        )
        jitted = jax.jit(step, in_shardings=in_shardings,
                         donate_argnums=(0, 2) if donate else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(trainable_c, frozen, opt_c, cbatch)
        return lowered

    # inference shapes: full params, standard sharding
    from ..models import model as model_lib
    params = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    p_shard = rules.params_shardings(params)

    if shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        step = steps_lib.make_prefill_step(cfg, cache_len(cfg, shape))
        args = (params, batch["tokens"])
        in_sh = (p_shard, NamedSharding(mesh, rules.batch_spec(
            batch["tokens"].shape)))
        if "embeds" in batch:
            args = args + (batch["embeds"],)
            in_sh = in_sh + (NamedSharding(mesh, rules.batch_spec(
                batch["embeds"].shape)),)
        jitted = jax.jit(step, in_shardings=in_sh)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
        return lowered

    # decode
    specs = input_specs(cfg, shape)
    step = steps_lib.make_decode_step(cfg)
    state_sh = rules.decode_state_shardings(specs["state"])
    tok_sh = NamedSharding(mesh, rules.batch_spec(specs["token"].shape))
    jitted = jax.jit(step, in_shardings=(p_shard, tok_sh, state_sh),
                     donate_argnums=(2,) if donate else ())
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params, specs["token"], specs["state"])
    return lowered


def lower_fed_round(arch: str, mesh,
                    train_spec: Optional[steps_lib.TrainSpec] = None,
                    unroll: bool = False, depth_blocks: Optional[int] = None):
    """Lower the ENTIRE federated round (Algorithm 1) as one SPMD program:
    T local GaLoreAdamW steps (scan) + FedAvg all-reduce over the client
    axes + the ṽ upload for server-side AJIVE — the paper's 𝒯→𝒜→𝒮 pipeline
    on the production mesh (train_4k geometry, per-client batch split by T).
    """
    import dataclasses as _dc
    shape = SHAPES["train_4k"]
    cfg = shape_variant(get_config(arch), shape)
    if unroll:
        cfg = _dc.replace(cfg, unroll_blocks=True, remat=False)
    if depth_blocks is not None:
        cfg = _dc.replace(cfg, n_layers=cfg.block_period() * depth_blocks)
    rules = ShardingRules(mesh, fsdp=True)
    rules_tp = ShardingRules(mesh, fsdp=False)
    batch_axes = _client_axes(mesh)
    spec = train_spec or steps_lib.TrainSpec()
    spec = _dc.replace(spec, client_axes=batch_axes)

    n_clients = 1
    for a in batch_axes:
        n_clients *= mesh.shape[a]
    t_steps = spec.local_steps
    per_client = max(shape.global_batch // (n_clients * t_steps), 1)
    trainable, frozen, opt_state = jax.eval_shape(
        lambda: steps_lib.init_train_state(jax.random.PRNGKey(0), cfg, spec))
    opt_c = _stack_opt_sds(opt_state, n_clients)
    cbatch = {
        "tokens": jax.ShapeDtypeStruct(
            (n_clients, t_steps, per_client, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct(
            (n_clients, t_steps, per_client, shape.seq_len), jnp.int32)}
    weights = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    step = steps_lib.make_fed_round_step(cfg, spec, n_clients)
    in_sh = (
        rules_tp.params_shardings(trainable),           # global: TP only
        rules.params_shardings(frozen),
        _client_opt_shardings(mesh, opt_c, batch_axes),
        jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P(batch_axes,
                                            *([None] * (x.ndim - 1)))),
            cbatch),
        NamedSharding(mesh, P()),
    )
    jitted = jax.jit(step, in_shardings=in_sh)
    with jax.set_mesh(mesh):
        return jitted.lower(trainable, frozen, opt_c, cbatch, weights)


def analyze(lowered, verbose: bool = True) -> Dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    result = {
        "compile_s": round(compile_s, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                            None),
        },
    }
    if verbose:
        print("  memory_analysis:", mem)
        print(f"  cost_analysis: flops={result['flops']:.3e} "
              f"bytes={result['bytes_accessed']:.3e}")
        print(f"  collectives: {coll}")
        print(f"  compile: {compile_s:.1f}s")
    return result


def analyze_combination(arch: str, shape_name: str, mesh, spec,
                        verbose: bool = True) -> Dict:
    """Full dry-run for one combination.

    The scanned (deployable, remat'd) program provides memory_analysis. True
    per-step FLOPs/bytes/collectives come from two *shallow unrolled* twins —
    1 block and 2 blocks of the repeating layer pattern, straight-line HLO —
    extrapolated as  cost(1) + (n_blocks-1)·(cost(2)-cost(1)). XLA counts
    while-loop bodies once regardless of trip count, and fully unrolling a
    60-layer MoE is compile-prohibitive; block extrapolation is exact because
    every block is structurally identical.
    """
    cfg = shape_variant(get_config(arch), SHAPES[shape_name])
    n_blocks = cfg.n_blocks()

    lowered = lower_combination(arch, shape_name, mesh, spec)
    res = analyze(lowered, verbose=False)

    r1 = analyze(lower_combination(arch, shape_name, mesh, spec, unroll=True,
                                   depth_blocks=1), verbose=False)
    if n_blocks > 1:
        r2 = analyze(lower_combination(arch, shape_name, mesh, spec,
                                       unroll=True, depth_blocks=2),
                     verbose=False)
    else:
        r2 = r1

    def extrap(f1, f2):
        # per-block delta clamped at 0: fusion across the 1->2 block boundary
        # can make cost(2) marginally smaller than cost(1) for tiny programs.
        return f1 + (n_blocks - 1) * max(f2 - f1, 0.0)

    coll = {k: int(extrap(r1["collective_bytes"][k],
                          r2["collective_bytes"][k]))
            for k in r1["collective_bytes"]}
    out = {
        "compile_s": res["compile_s"] + r1["compile_s"] + r2["compile_s"],
        "flops": extrap(r1["flops"], r2["flops"]),
        "bytes_accessed": extrap(r1["bytes_accessed"], r2["bytes_accessed"]),
        "collective_bytes": coll,
        "memory": res["memory"],
        "scanned_flops": res["flops"],
        "n_blocks": n_blocks,
    }
    if verbose:
        print(f"  memory(argument/temp): {out['memory']['argument_bytes']:.3e} "
              f"/ {out['memory']['temp_bytes']:.3e} B")
        print(f"  cost (unrolled): flops={out['flops']:.3e} "
              f"bytes={out['bytes_accessed']:.3e}")
        print(f"  collectives: {out['collective_bytes']}")
        print(f"  compile: {out['compile_s']:.1f}s")
    return out


def fake_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices, the production mesh's size. Call
    before the first JAX operation: the flag is read when the backend
    starts."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={n}").strip()


def main(argv=None):
    fake_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape)")
    ap.add_argument("--round", dest="fed_round", action="store_true",
                    help="lower the FULL federated round (T local steps + "
                         "FedAvg all-reduce + ṽ upload) instead of one step")
    ap.add_argument("--out", default=None, help="write JSON results")
    ap.add_argument("--rank", type=int, default=64)
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    spec = steps_lib.TrainSpec(rank=args.rank)

    if args.fed_round:
        assert args.arch, "--round requires --arch"
        tag = f"{args.arch}@fed_round@{args.mesh}"
        print(f"== {tag} ==", flush=True)
        lowered = lower_fed_round(args.arch, mesh, spec)
        res = analyze(lowered)
        if args.out:
            with open(args.out, "w") as f:
                json.dump({tag: res}, f, indent=1)
        print("ALL OK")
        return

    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos.append((args.arch, args.shape))

    results = {}
    failures = []
    for arch, shape_name in combos:
        tag = f"{arch}@{shape_name}@{args.mesh}"
        print(f"== {tag} ==", flush=True)
        try:
            results[tag] = analyze_combination(arch, shape_name, mesh, spec)
        except Exception as e:  # noqa: BLE001 — report, keep going
            print(f"  FAILED: {type(e).__name__}: {e}")
            failures.append(tag)
            results[tag] = {"error": f"{type(e).__name__}: {e}"}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("ALL OK")


if __name__ == "__main__":
    main()

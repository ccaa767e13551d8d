"""Shared pieces of the benchmark harness: manifest and file lookup by name,
the device check, the peak table, the program's import path, the weights
made from the seed, and the result line.

Everything that belongs to one configuration, cell, driver kind or
per-layer metric lives in a file of its own that is found by its name:

    bench/configs/<config>.json     sizes as run, source, reduced, assumed
    bench/cells/<cell>.json         driver kind and every traffic parameter
    bench/drivers/<kind>.py         ``run(ctx) -> dict``
    bench/metrics/<metric>.py       ``read(trace, ctx) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Any, Dict, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, man: Optional[Dict] = None) -> Dict:
    man = man or manifest()
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def cell_file(name: str) -> Dict:
    return load_json(BENCH / "cells" / f"{name}.json")


def config_file(name: str, man: Optional[Dict] = None) -> Dict:
    man = man or manifest()
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"no config named {name!r} in BENCHMARK.json")


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (names may hold dots, so not by import)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def program_path() -> None:
    """Put the system under test (``src/``) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds are an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def arch_config(conf: Dict):
    """The program's ArchConfig built from the configuration file's
    ``arch`` block (the sizes as run)."""
    program_path()
    from repro.configs.base import ArchConfig
    return ArchConfig(**conf["arch"])


def seed_key(seed: int):
    """A PRNG key from a seed of any size, 64-bit ones included."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def weight_std(path: str) -> tuple:
    """(mean, std) of a weight leaf by its role: norm scales near one,
    everything else small normal values."""
    last = path.split("/")[-1]
    if last == "scale":
        return 1.0, 0.02
    return 0.0, 0.02


def make_weights(arch, seed: int):
    """Weights in the program's layout and dtype, drawn from the seed on the
    device in one jitted call. Only the layout (shapes and paths) is taken
    from the program; every value is the benchmark's own."""
    program_path()
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    shapes = jax.eval_shape(lambda k: model_lib.init_params(k, arch),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(q, "key", getattr(q, "idx", q)))
                      for q in p) for p, _ in flat]

    def make(key):
        out = []
        for i, ((_, s), path) in enumerate(zip(flat, paths)):
            mean, std = weight_std(path)
            x = mean + std * jax.random.normal(jax.random.fold_in(key, i),
                                               s.shape, jnp.float32)
            out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))

"""Readings for setting a cell's limits, on the chip, in one process (so
that set-up and compilation are paid once):

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--faults half_batch]

Per seed, against one plain reference: the program's compared numbers, the
control's (the reference put in the program's place in the precision
below the configuration's), and the program's with each named fault of
``bench/faults.py`` planted. Each set of numbers is judged against the
cell's limits as a run judges its own. Every seed's result is one JSON
line on standard output.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import common  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    planted = [f for f in args.faults.split(",") if f]
    unknown = set(planted) - set(faults.NAMES)
    if unknown:
        raise SystemExit(f"unknown faults {sorted(unknown)}; "
                         f"known: {faults.NAMES}")

    common.program_path()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    man = common.manifest()
    wl = common.workload(args.workload, man)
    cell = common.cell_file(args.workload)
    conf = common.config_file(wl["config"], man)
    dev = run.device_check(wl["chips"])
    drv = common.driver(cell["driver"])
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        ctx = run.Ctx(args.workload, cell, conf, seed, 0.0, False,
                      wl["chips"], t0=time.perf_counter())
        ctx.device_kind = dev["kind"]
        t0 = time.perf_counter()
        got = drv.readings(ctx, planted)
        out = {}
        for who, values in got.items():
            checks, correct = run.judge(values, cell["limits"])
            out[who] = {"correct": correct,
                        **{k: c["value"] for k, c in checks.items()}}
        print(json.dumps({"seed": seed, "readings": out,
                          "seconds": time.perf_counter() - t0,
                          "device": dev}), flush=True)


if __name__ == "__main__":
    main()

"""Benchmark entry point: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root on a machine that holds the cell's chips. The
cell (``bench/cells/<name>.json``) names its driver kind
(``bench/drivers/<kind>.py``) and its traffic; its configuration comes from
``BENCHMARK.json``. Set-up (weights from the seed, programs from the
compile cache, warm-up) is timed as ``setup_s``; the window then runs for
``--seconds``; the program's state is freed and its output is compared with
the plain reference. ``--trace 1`` profiles part of the window and reports
the cell's per-layer metrics instead of its end-to-end ones.

Progress goes to earlier lines of standard output; the last line is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), with every compared number
beside its limit under ``checks``, last. The same comparisons close
standard error. On a host without the cell's TPU chips the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import common  # noqa: E402

COMPILES = [0]     # programs compiled, not found in the compile cache


def _count_compiles(event: str, duration: float, **_) -> None:
    # JAX times every request for a program this way, cache hits included
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES[0] += 1


def _count_cache_hits(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        COMPILES[0] -= 1


class Ctx:
    """What a driver sees of the run: the cell, its configuration, the
    seed and length, and the hooks for set-up, tracing and memory."""

    def __init__(self, name, cell, conf, seed, seconds, trace, chips,
                 t0=None):
        self.name, self.cell, self.conf = name, cell, conf
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips = chips
        self.t0 = T0 if t0 is None else t0
        self.setup_s = None
        self.trace_dir = None
        self.trace_work = None
        self.memory_peak = 0
        self._span = None

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0
        self.compiles_at_setup = COMPILES[0]
        self.log(f"set-up done: {self.setup_s:.4f} s, {COMPILES[0]} "
                 "programs compiled (not found in the cache)")

    def trace_start(self) -> None:
        import jax
        self.trace_dir = (common.ROOT / ".bench_traces" /
                          f"{self.name}-{self.seed}")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # keep the benchmark's own spans
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def trace_stop(self, work) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.trace_work = work

    def read_memory(self) -> None:
        import jax
        self.log(f"programs compiled in the window: "
                 f"{COMPILES[0] - self.compiles_at_setup}")
        devs = jax.devices()[: self.chips]
        self.memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs)


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is a {dev['platform']} "
                         "device; the benchmark runs only on TPU chips")
    if dev["count"] < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, JAX reports "
                         f"{dev['count']}")
    common.peaks(dev["kind"])
    return dev


def per_layer_metrics(man, name, cell_e2e, ctx, summary):
    out = {}
    for m in man["per_layer"]:
        listed = m.get("workloads")
        if listed is not None and name not in listed:
            continue
        if listed is None and m["moves"] not in cell_e2e:
            continue
        value = common.metric_reader(m["name"]).read(summary, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(values, limits, failed=0):
    """Each compared number beside its limit, and whether all are within
    them (a number that is not finite is not)."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    correct = (failed == 0 and
               all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in checks.values()))
    return checks, correct


def run_cell(name, cell, conf, man, seed, seconds, trace, chips,
             require_chip=True, t0=None):
    """One run; returns the result object (the last line's content)."""
    common.program_path()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not COMPILES[1:]:
        jax.monitoring.register_event_duration_secs_listener(_count_compiles)
        jax.monitoring.register_event_listener(_count_cache_hits)
        COMPILES.append(True)
    if require_chip:
        dev = device_check(chips)
    else:
        d = jax.devices()[0]
        dev = {"platform": d.platform, "kind": d.device_kind,
               "count": len(jax.devices())}
    ctx = Ctx(name, cell, conf, seed, seconds, trace, chips, t0=t0)
    ctx.device_kind = dev["kind"]
    res = common.driver(cell["driver"]).run(ctx)

    checks, correct = judge(res["checks"], cell["limits"], res["failed"])
    cell_e2e = [m["name"] for m in man["end_to_end"]
                if name in m.get("workloads", [name])]
    device = dict(dev, memory_peak_bytes=ctx.memory_peak)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    if trace:
        import trace_reduce
        summary = trace_reduce.reduce(ctx.trace_dir, chips=chips)
        summary["work"] = ctx.trace_work
        metrics = per_layer_metrics(man, name, cell_e2e, ctx, summary)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out.update(metrics=metrics, device=device,
                   breakdown=summary["breakdown"])
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in man["end_to_end"]}
        values = dict(res["e2e"], setup_s=ctx.setup_s)
        out.update(metrics={k: {"value": v, "unit": units[k]}
                            for k, v in values.items() if k in cell_e2e},
                   device=device)
    out["checks"] = checks
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = common.manifest()
    wl = common.workload(args.workload, man)
    cell = common.cell_file(args.workload)
    conf = common.config_file(wl["config"], man)
    out = run_cell(args.workload, cell, conf, man, args.seed, args.seconds,
                   bool(args.trace), wl["chips"])
    for k, c in out["checks"].items():
        print(f"{k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Run every paper-table benchmark. Prints ``name,us_per_call,derived`` CSV.

One benchmark per paper artifact:
  Tables 3/4/5  -> bench_fed_methods      (IID vs Dirichlet-0.5 across methods)
  Table 6/Fig3ab-> bench_landscape        (kinetic-trap basin fractions)
  Fig 3c        -> bench_interpolation    (client-model loss barriers)
  Fig 1 right   -> bench_state_mismatch   (local vs global progress)
  Fig 4/App. D  -> bench_projector_schedule
  Fig 5/App. F  -> bench_ajive_recovery
  Table 7       -> bench_ajive_latency
  Table 2       -> bench_comm
  §Roofline     -> roofline (reads dryrun_single.json when present)
  Serving       -> bench_serve (scan decode, hetero adapters, slot batching)
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def _env_hygiene() -> None:
    """Launcher hygiene, applied BEFORE jax initializes (mirrors the shell
    block in scripts/ci.sh): tcmalloc preload can't be done from in-process
    (LD_PRELOAD is read at exec), but the allocator threshold, C++ log
    level, and XLA host-device plumbing are env-var driven and honored at
    first jax import."""
    os.environ.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                          "60000000000")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    flags = []
    host_devices = os.environ.get("REPRO_HOST_DEVICES")
    if host_devices:
        flags.append(f"--xla_force_host_platform_device_count={host_devices}")
    # Opt-in only: rejected by CPU builds of XLA (unknown-flag error).
    if os.environ.get("REPRO_STEP_MARKERS") == "1":
        flags.append("--xla_step_marker_location=1")
    if flags:
        prev = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (prev + " " + " ".join(flags)).strip()


def main() -> None:
    _env_hygiene()
    from . import (bench_ajive_latency, bench_ajive_recovery, bench_comm,
                   bench_fed_methods, bench_interpolation, bench_landscape,
                   bench_participation, bench_projector_schedule,
                   bench_serve, bench_state_mismatch)

    print("name,us_per_call,derived")
    suites = [
        ("serve", bench_serve.main),
        ("ajive_latency", bench_ajive_latency.main),
        ("ajive_recovery", bench_ajive_recovery.main),
        ("comm", bench_comm.main),
        ("landscape", bench_landscape.main),
        ("projector_schedule", bench_projector_schedule.main),
        ("state_mismatch", bench_state_mismatch.main),
        ("interpolation", bench_interpolation.main),
        ("fed_methods", bench_fed_methods.main),
        ("participation", bench_participation.main),
    ]
    failures = []
    for name, fn in suites:
        t0 = time.time()
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
        print(f"# suite {name} done in {time.time() - t0:.1f}s",
              file=sys.stderr)

    if os.path.exists("dryrun_single.json"):
        from . import roofline
        rows = roofline.analyze("dryrun_single.json")
        for r in rows:
            print(f"roofline/{r['arch']}@{r['shape']},"
                  f"{r['bound_s'] * 1e6:.1f},"
                  f"dominant={r['dominant']};useful={r['useful_ratio']:.2f}")
    else:
        print("# roofline skipped: run repro.launch.dryrun --all first",
              file=sys.stderr)

    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Dry-run 'profiler': per-op breakdown of the post-SPMD HLO.

No wall-clock exists on this container, so the profile is structural: every
instruction's output-buffer bytes grouped by opcode, plus the top individual
collectives / dots / fusions with their shapes. This is what the §Perf
hypothesis loop reads instead of a trace.

Usage:
  PYTHONPATH=src python -m benchmarks.hlo_profile --arch command-r-35b \
      --shape decode_32k [--mesh single] [--top 15]
"""
from __future__ import annotations

import argparse
import re
from collections import defaultdict

_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = "
                       r"(?:\(([^)]*)\)|(\w+)\[([\d,]*)\]\S*)\s+([\w-]+)")
_INNER_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


def _bytes(dtype, dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def profile_text(hlo: str, top: int = 15):
    by_op = defaultdict(int)
    biggest = []
    for line in hlo.splitlines():
        m = _SHAPE_RE.match(line)
        if not m:
            continue
        tup, dtype, dims, op = m.groups()
        if op in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast"):
            continue
        if tup is not None:
            size = sum(_bytes(d, s) for d, s in _INNER_SHAPE.findall(tup))
            shape_str = "(tuple)"
        else:
            size = _bytes(dtype, dims)
            shape_str = f"{dtype}[{dims}]"
        by_op[op] += size
        biggest.append((size, op, shape_str, line.strip()[:140]))
    biggest.sort(reverse=True)
    return by_op, biggest[:top]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--unroll", action="store_true", default=True)
    ap.add_argument("--blocks", type=int, default=1,
                    help="depth_blocks for the unrolled twin")
    args = ap.parse_args(argv)

    from repro.launch import dryrun
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import TrainSpec

    dryrun.fake_host_devices()
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    lowered = dryrun.lower_combination(args.arch, args.shape, mesh,
                                       TrainSpec(rank=64), unroll=True,
                                       depth_blocks=args.blocks)
    compiled = lowered.compile()
    by_op, biggest = profile_text(compiled.as_text(), args.top)

    print(f"== {args.arch}@{args.shape}@{args.mesh} "
          f"(unrolled, {args.blocks} block(s)) ==")
    print("\n-- output bytes by opcode --")
    for op, size in sorted(by_op.items(), key=lambda kv: -kv[1])[:20]:
        print(f"  {op:24s} {size / 2**30:10.3f} GiB")
    print(f"\n-- top {args.top} single ops --")
    for size, op, shape, line in biggest:
        print(f"  {size / 2**30:8.3f} GiB {op:16s} {shape:28s} {line[:90]}")
    cost = compiled.cost_analysis()
    print(f"\nflops={cost.get('flops', 0):.3e} "
          f"bytes={cost.get('bytes accessed', 0):.3e}")


if __name__ == "__main__":
    main()

"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- ``busy_s``: per chip, the union of the intervals in which an operation
  ran on the device (the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane), inside the traced window; averaged over the chips used.
- ``window_s``: the length of the host span ``bench.window``.
- ``ops``: per device operation name, its count, its total device time
  in the window (``seconds``) and its self time (``self_seconds``: less
  the time of the operations nested inside it on the same line, as a
  loop's body ops are inside the loop), summed over the chips used.
- ``collective_s`` / ``exposed_collective_s``: the time of collective
  operations (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute) and the part of it in which no other operation ran,
  averaged over the chips.
- ``breakdown``: the ten device operations (by HLO instruction name)
  that took most self time, and the ten longest idle gaps, each named by the
  innermost host span (the benchmark's ``TraceAnnotation``) that was open
  at its middle.

A device op's name in the trace is its HLO text, so ``shapes`` reads its
operand and output shapes from it. The device clock runs about a
millisecond behind the host's in the traces seen on a v5e; a gap's name is
taken at its middle, so only gaps of that order can be misnamed.
"""
from __future__ import annotations

import pathlib
import re
from typing import Dict, List, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "allreduce", "allgather")
WINDOW_SPAN = "bench.window"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Total length of union(a) minus its overlap with union(b)."""
    a, b = _union(a), _union(b)
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


_TYPE = re.compile(r"\b(bf16|f16|f32|f64|s8|u8|s16|s32|u32|s64|pred|"
                   r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
          "s16": 2, "s32": 4, "u32": 4, "s64": 8, "pred": 1,
          "f8e4m3fn": 1, "f8e5m2": 1}


def short_name(name: str) -> str:
    """``%lowrank_linear.3 = bf16[...] custom-call(...)`` -> the HLO
    instruction's name, ``lowrank_linear.3``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """The instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", short_name(name))


def shapes(name: str) -> Tuple[List, List]:
    """(output, operands) of a device op named by its HLO text: each a list
    of (dtype, dims, bytes)."""
    if " = " not in name:
        return [], []
    rhs = name.split(" = ", 1)[1]
    head, _, rest = rhs.partition("(")
    args = rest.split("), ", 1)[0]

    def parse(text):
        out = []
        for dt, dims in _TYPE.findall(text):
            d = [int(x) for x in dims.split(",") if x]
            n = 1
            for x in d:
                n *= x
            out.append((dt, d, n * _BYTES[dt]))
        return out
    return parse(head), parse(args)


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(c in low for c in COLLECTIVES)


def load(path) -> "object":
    import jax
    path = pathlib.Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    return jax.profiler.ProfileData.from_file(str(path))


def _self_times(evs) -> List[int]:
    """Each event's duration less that of the events nested directly in
    it (events of one line nest: a loop or a conditional holds the ops of
    its body)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [e - s for _, s, e in evs]
    stack: List[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return own


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def device_ops(pd, chips: int) -> List[List[Tuple[str, int, int]]]:
    """Per chip, the events of its ``XLA Ops`` line."""
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    out = []
    for p in planes[:chips]:
        for line in p.lines:
            if line.name == "XLA Ops":
                out.append(_events(line))
                break
        else:
            out.append([])
    return out


def host_spans(pd) -> List[Tuple[str, int, int]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for name, s, e in _events(line):
                if name.startswith("bench."):
                    spans.append((name, s, e))
    return spans


def reduce(path, chips: int = 1) -> Dict:
    pd = load(path)
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    per_chip = device_ops(pd, chips)
    busy, coll, exposed = [], [], []
    ops: Dict[str, List[float]] = {}
    for evs in per_chip:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
               if e > lo and s < hi]
        busy.append(_length(_union([(s, e) for _, s, e in evs])))
        c = [(s, e) for n, s, e in evs if is_collective(n)]
        other = [(s, e) for n, s, e in evs if not is_collective(n)]
        coll.append(_length(_union(c)))
        exposed.append(_subtract(c, other))
        for (n, s, e), own in zip(evs, _self_times(evs)):
            acc = ops.setdefault(n, [0, 0.0, 0.0])
            acc[0] += 1
            acc[1] += (e - s) * 1e-9
            acc[2] += own * 1e-9
    n_chips = max(len(per_chip), 1)
    gaps = []
    if per_chip:
        ev0 = [(max(s, lo), min(e, hi)) for _, s, e in per_chip[0]
               if e > lo and s < hi]
        cur = lo
        for s, e in _union(ev0) + [(hi, hi)]:
            if s > cur:
                mid = (s + cur) // 2
                open_ = [(n, ss, ee) for n, ss, ee in inner
                         if ss <= mid <= ee]
                name = (min(open_, key=lambda x: x[2] - x[1])[0]
                        if open_ else "no span")
                gaps.append([name, (s - cur) * 1e-9])
            cur = max(cur, e)
    gaps.sort(key=lambda g: -g[1])
    by_short: Dict[str, float] = {}
    for n, v in ops.items():
        k = short_name(n)
        by_short[k] = by_short.get(k, 0.0) + v[2]
    top = sorted(by_short.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_chips * 1e-9,
        "collective_s": sum(coll) / n_chips * 1e-9,
        "exposed_collective_s": sum(exposed) / n_chips * 1e-9,
        "ops": {n: {"count": v[0], "seconds": v[1], "self_seconds": v[2]}
                for n, v in ops.items()},
        "breakdown": {"device_ops": [[n, v] for n, v in top],
                      "idle_gaps": gaps[:10]},
    }

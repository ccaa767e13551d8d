"""Stage scopes, host spans and one clock for a traced round window: what
``trace_reduce.py`` leaves out of a profiler trace.

- **Clock.** Per chip, the offset that puts the device clock on the host's,
  from the programs that both sides of the trace name by ``run_id``. The
  host enqueues a program (``DoEnqueueProgram``) before its ``XLA Modules``
  run starts on the device, and ends its callbacks (``CompleteCallbacks``)
  after the run ends, so host = device + offset with the offset in
  [max(enqueue start - module start), min(callback end - module end)].
  ``clock_offset_s`` is the middle of that interval and
  ``clock_offset_spread_s`` its width. A trace without such pairs (a CPU
  trace) is not shifted: ``clock_aligned`` is false.
- **Host spans.** The benchmark's ``bench.*`` and the program's ``fed.*``
  ``TraceAnnotation``s. Each idle gap of the device (the same gaps as
  ``trace_reduce``) is named by the innermost span open at its middle on
  the aligned clock.
- **Stage scopes.** Each instruction of the round program takes the
  innermost stage scope (a ``jax.named_scope`` named ``fed.*``,
  ``model.*``, ``lowrank.*`` or ``galore.*``) in its ``op_name``, read from
  the compiled program's HLO text, since the trace's ops carry no
  ``op_name``. An instruction with none takes the scopes of the instruction
  that calls its computation (a loop's or a conditional's body). Device ops
  are assigned to the ``XLA Modules`` run that holds them; over the round
  program's runs inside the window, their self times (``trace_reduce``) are
  summed per scope. Ops of other programs are kept apart.

``for_run(ctx)`` reduces a traced run of the ``round`` driver once and
logs the whole table; the per-layer metrics read it.
"""
from __future__ import annotations

import bisect
import json
import re
from typing import Dict, List, Optional, Tuple

import common
import trace_reduce

HOST_SPANS = ("bench.", "fed.")
STAGE = re.compile(r"(fed|model|lowrank|galore)\.[a-z_]+")
OTHER = "other programs"
NO_SCOPE = "no scope"
UNMAPPED = "not in the program's HLO"

_WRAP = re.compile(r"^[\w\-]+\((.*)\)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_CALLED_SET = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")


# ------------------------------------------------------------ the program --
def unwrap(component: str) -> str:
    """``transpose(jvp(model.head))`` -> ``model.head``: the name inside the
    transformations JAX wraps around a scope's name."""
    m = _WRAP.match(component)
    while m:
        component = m.group(1)
        m = _WRAP.match(component)
    return component


def stage_scopes(op_name: str) -> List[str]:
    """The stage scopes in an ``op_name``, outermost first."""
    return [c for c in map(unwrap, op_name.split("/")) if STAGE.fullmatch(c)]


def hlo_scopes(text: str) -> Tuple[str, Dict[str, List[str]]]:
    """(module name, {instruction name: its stage scopes, outermost first})
    of a compiled program's HLO text."""
    module, entry, cur = None, None, None
    comps: Dict[str, list] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line.startswith((" ", "\t")):
            m = _INSTR.match(line)
            if m and cur is not None:
                op = _OP_NAME.search(line)
                called = _CALLED.findall(line)
                for group in _CALLED_SET.findall(line):
                    called += [c.strip().lstrip("%") for c in group.split(",")
                               if c.strip()]
                cur.append((m.group(1),
                            stage_scopes(op.group(1)) if op else [], called))
        else:
            m = _COMP.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            if m and line.startswith("ENTRY"):
                entry = m.group(1)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    scopes: Dict[str, List[str]] = {}
    inherited = {entry: []}
    todo = [entry]
    while todo:
        comp = todo.pop()
        for name, own, called in comps.get(comp, ()):
            path = own or inherited[comp]
            scopes[name] = path
            for c in called:
                if c not in inherited:
                    inherited[c] = path
                    todo.append(c)
    return module, scopes


def has_stages(scopes: Dict[str, List[str]]) -> bool:
    return any(p.startswith("fed.") for path in scopes.values() for p in path)


# -------------------------------------------------------------- the trace --
def host_spans(pd) -> List[Tuple[str, int, int, Dict]]:
    """(name, start, end, stats) of every ``bench.*`` and ``fed.*`` span."""
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name.startswith(HOST_SPANS):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def device_planes(pd, chips: int) -> list:
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:chips]


def module_runs(plane) -> List[Tuple[str, int, int, Optional[int]]]:
    """(program, start, end, run_id) of each run on a device plane's
    ``XLA Modules`` line; the program is the event's name without its
    ``(fingerprint)``."""
    for line in plane.lines:
        if line.name == "XLA Modules":
            return [(e.name.split("(", 1)[0], int(e.start_ns),
                     int(e.start_ns + e.duration_ns),
                     dict(e.stats).get("run_id")) for e in line.events]
    return []


def host_run_bounds(pd) -> Dict[Tuple[int, int], List[Optional[int]]]:
    """{(device ordinal, run_id): [first enqueue start, first callback
    end]} from the host's runtime events."""
    out: Dict[Tuple[int, int], List[Optional[int]]] = {}
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == "DoEnqueueProgram":
                    i, t = 0, int(e.start_ns)
                elif e.name == "CompleteCallbacks":
                    i, t = 1, int(e.start_ns + e.duration_ns)
                else:
                    continue
                st = dict(e.stats)
                if "run_id" not in st:
                    continue
                key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                slot = out.setdefault(key, [None, None])
                slot[i] = t if slot[i] is None else min(slot[i], t)
    return out


def clock_offset(runs, bounds, ordinal: int) -> Optional[Tuple[int, int]]:
    """(low, high) of the device-to-host offset of one chip in ns, or None
    where no run is named on both sides."""
    lows, highs = [], []
    for _, s, e, run_id in runs:
        enq, done = bounds.get((ordinal, run_id), (None, None))
        if enq is not None:
            lows.append(enq - s)
        if done is not None:
            highs.append(done - e)
    if not lows or not highs:
        return None
    lo, hi = max(lows), min(highs)
    if lo > hi:
        raise ValueError(
            f"chip {ordinal}: host and device events disagree: the programs' "
            f"enqueues put the device clock at least {lo} ns behind the "
            f"host's, their callbacks at most {hi} ns")
    return lo, hi


def idle_gaps(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The intervals of [lo, hi] in which no op ran (trace_reduce's gaps)."""
    busy = trace_reduce._union([(max(s, lo), min(e, hi)) for _, s, e in ops
                                if e > lo and s < hi])
    gaps, cur = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    return gaps


def innermost_span(spans, t: int) -> str:
    open_ = [(n, s, e) for n, s, e, _ in spans if s <= t <= e]
    return min(open_, key=lambda x: x[2] - x[1])[0] if open_ else "no span"


def scope_seconds(ops, runs, scopes, program: str, lo: int, hi: int,
                  offset: int) -> Tuple[Dict[str, float], int, Dict]:
    """Self seconds per scope of one chip's ops (``XLA Ops`` events, device
    clock) over the ``program``'s runs that lie in the host window
    [lo, hi] once shifted by ``offset``; ops of other programs' runs in the
    window go under ``OTHER``. Returns (seconds by scope, runs counted,
    {round_busy_s, unstaged_s})."""
    inside = [(s, e, p == program) for p, s, e, _ in runs
              if s + offset >= lo and e + offset <= hi]
    inside.sort()
    starts = [s for s, _, _ in inside]
    out: Dict[str, float] = {}
    busy, unstaged = [], 0
    for (name, s, e), own in zip(ops, trace_reduce._self_times(ops)):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= inside[i][1]:
            continue
        if not inside[i][2]:
            key = OTHER
        else:
            busy.append((s, e))
            path = scopes.get(trace_reduce.short_name(name))
            if path is None:
                key = UNMAPPED
            else:
                key = path[-1] if path else NO_SCOPE
            if not any(p.startswith("fed.") for p in path or ()):
                unstaged += own
        out[key] = out.get(key, 0.0) + own * 1e-9
    n_runs = sum(1 for _, _, mine in inside if mine)
    return out, n_runs, {
        "round_busy_s": trace_reduce._length(trace_reduce._union(busy)) * 1e-9,
        "unstaged_s": unstaged * 1e-9}


def summarize(pd, chips: int, rounds: int, program: str,
              scopes: Dict[str, List[str]]) -> Dict:
    """The clock, the scope table and the named idle gaps of a traced
    window of ``rounds`` runs of ``program``. Seconds are summed over the
    traced rounds and averaged over the chips."""
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e, _ in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]
    inner = [x for x in spans if x[0] != trace_reduce.WINDOW_SPAN]
    bounds = host_run_bounds(pd)
    planes = device_planes(pd, chips)
    per_op = trace_reduce.device_ops(pd, chips)
    clock, totals, extra, gaps = [], {}, {"round_busy_s": 0.0,
                                          "unstaged_s": 0.0}, []
    for k, (plane, ops) in enumerate(zip(planes, per_op)):
        ordinal = int(plane.name.rsplit(":", 1)[1])
        runs = module_runs(plane)
        interval = clock_offset(runs, bounds, ordinal)
        offset = 0 if interval is None else (interval[0] + interval[1]) // 2
        clock.append({"chip": ordinal, "clock_aligned": interval is not None,
                      "clock_offset_s": offset * 1e-9,
                      "clock_offset_spread_s": (
                          0.0 if interval is None
                          else (interval[1] - interval[0]) * 1e-9)})
        secs, n_runs, more = scope_seconds(ops, runs, scopes, program, lo, hi,
                                           offset)
        if n_runs != rounds:
            raise ValueError(
                f"chip {ordinal}: {n_runs} runs of {program} inside the "
                f"traced window, {rounds} rounds traced")
        for key, v in secs.items():
            totals[key] = totals.get(key, 0.0) + v / len(planes)
        for key, v in more.items():
            extra[key] += v / len(planes)
        if k == 0:
            gaps = sorted(([innermost_span(inner, (s + e) // 2 + offset),
                            (e - s) * 1e-9] for s, e in idle_gaps(ops, lo, hi)),
                          key=lambda g: -g[1])[:10]
    busy = extra["round_busy_s"]
    if totals.get(UNMAPPED, 0.0) > 0.01 * busy:
        raise ValueError(
            f"{totals[UNMAPPED]:.4f} s of {program}'s {busy:.4f} s ran in ops "
            "that its compiled HLO text does not name: the text read is not "
            "the program that ran")
    return {"program": program, "rounds": rounds, "clock": clock,
            "scopes": sorted(([k, v] for k, v in totals.items()),
                             key=lambda kv: -kv[1]),
            "round_busy_s": busy, "unstaged_s": extra["unstaged_s"],
            "idle_gaps": gaps}


# ---------------------------------------------------------------- the run --
def round_program_text(ctx) -> str:
    """The compiled HLO text of the program the window ran, rebuilt from the
    cell as the ``round`` driver builds it: same weights' shapes, engine and
    batch shapes, so the compile finds the program set-up compiled or
    loaded. The compile cache's key leaves source locations out, and with
    them the scopes' names, so where nothing else tells the programs apart
    (on the CPU; on a v5e the Pallas kernels' bodies do) an entry written
    before the scopes existed comes back without them. The program is then
    compiled again under a key that includes them, and kept only if its
    instructions are those that ran."""
    import jax
    drv = common.driver(ctx.cell["driver"])
    arch = common.arch_config(ctx.conf)
    batch = drv.device_round(drv.traffic_of(ctx.cell, ctx.conf, ctx.seed), 0)

    def compiled_text():
        # a new engine, so that nothing of an earlier lowering is reused
        engine = drv.build_engine(arch, common.make_weights(arch, ctx.seed),
                                  ctx.cell["fed"])
        return engine.lower_round(batch).compile().as_text()

    def recompile():
        key = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            return compiled_text()
        finally:
            jax.config.update(key, was)

    return staged_text(compiled_text(), recompile)


def staged_text(text: str, recompile) -> str:
    """``text`` where it carries the stage scopes; else ``recompile()``'s
    text, if that carries them and names the same instructions."""
    module, scopes = hlo_scopes(text)
    if has_stages(scopes):
        return text
    fresh = recompile()
    module2, scopes2 = hlo_scopes(fresh)
    if not has_stages(scopes2):
        raise ValueError(f"{module2} carries no fed.* stage scope")
    if module2 != module or sorted(scopes2) != sorted(scopes):
        raise ValueError(
            f"{module} came from a compile-cache entry built before the "
            "program's stage scopes existed, and a fresh compile gives other "
            "instructions: clear the compile cache and trace again")
    return fresh


def for_run(ctx) -> Optional[Dict]:
    """The reduction of a traced run of the ``round`` driver, made once per
    run and logged whole; None where there is nothing to read: no device
    trace, or a program without the ``fed.round`` spans and scopes."""
    if not hasattr(ctx, "trace_scopes"):
        ctx.trace_scopes = _reduce_run(ctx)
    return ctx.trace_scopes


def _reduce_run(ctx) -> Optional[Dict]:
    pd = trace_reduce.load(ctx.trace_dir)
    if not any(n == "fed.round" for n, _, _, _ in host_spans(pd)):
        return None
    planes = device_planes(pd, ctx.chips)
    if not planes or not any(module_runs(p) for p in planes):
        return None
    module, scopes = hlo_scopes(round_program_text(ctx))
    out = summarize(pd, ctx.chips, ctx.trace_work["rounds"], module, scopes)
    ctx.log("trace scopes: " + json.dumps(out))
    return out


def ms_per_round(ctx, scope: str) -> Optional[float]:
    """Self time of one scope per traced round, in ms."""
    out = for_run(ctx)
    if out is None:
        return None
    return 1e3 * dict(out["scopes"]).get(scope, 0.0) / out["rounds"]

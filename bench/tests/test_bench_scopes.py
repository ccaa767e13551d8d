"""Stage scopes, host spans and the clock (bench/trace_scopes.py): the
program's compiled round carries its scopes, the recorded chip trace gives
the clock offset, attribution on a synthetic trace, and a CPU profile of two
rounds."""
import re
import types

import jax
import jax.numpy as jnp
import pytest

import common
import trace_reduce
import trace_scopes as ts
from drivers import round as drv
from helpers_tiny import tiny

TRACE = common.BENCH / "traces" / "probe-v5e.xplane.pb"
STAGES = ("fed.init_state", "fed.local", "fed.guard", "fed.aggregate",
          "fed.sync", "model.attention", "model.head", "lowrank.apply",
          "lowrank.norm_probe", "galore.refresh", "galore.update")


def tiny_engine(**fed_cfg):
    common.program_path()
    from repro.core.fed import FedConfig, FedEngine
    from repro.launch.steps import galore_target_fn
    from repro.models import model as model_lib
    cell, conf = tiny("round")
    arch = common.arch_config(conf)
    fed = cell["fed"]
    cfg = FedConfig(rank=fed["rank"], lr=fed["lr"],
                    local_steps=fed["local_steps"],
                    client_chunk=fed["client_chunk"], **fed_cfg)
    engine = FedEngine(cfg, lambda p, b: model_lib.loss_fn(p, arch, b),
                       common.make_weights(arch, 7),
                       target_fn=galore_target_fn(arch))
    batch = drv.device_round(drv.traffic_of(cell, conf, 7), 0)
    return engine, batch


@pytest.fixture(scope="module")
def guarded_round_text():
    """The compiled guarded round of a syncing lift-free method: every
    stage the round program has."""
    engine, batch = tiny_engine(method="fedgalore", quarantine=True)
    assert engine._lift_free
    k = jax.tree_util.tree_leaves(batch)[0].shape[0]
    engine._ensure_client_buffers(k)
    lowered = engine._round_guard_jitted().lower(
        engine._client_state, engine._client_opt, engine.global_trainable,
        engine.frozen, engine.synced_v, jnp.asarray(0, jnp.int32), batch,
        engine._normalize_weights(None, k), jnp.ones((k,), jnp.float32))
    return lowered.compile().as_text()


def test_round_program_carries_every_stage_scope(guarded_round_text):
    module, scopes = ts.hlo_scopes(guarded_round_text)
    assert module == "jit_round_fn"
    found = {s for path in scopes.values() for s in path}
    assert set(STAGES) <= found, set(STAGES) - found


def test_every_contraction_is_under_a_stage(guarded_round_text):
    _, scopes = ts.hlo_scopes(guarded_round_text)
    ops = re.findall(r"^\s+(?:ROOT\s+)?%?(\S+) = .*? "
                     r"(dot|convolution|custom-call)\(",
                     guarded_round_text, re.M)
    assert any(op == "dot" for _, op in ops)
    unstaged = [(n, op) for n, op in ops
                if not any(s.startswith("fed.") for s in scopes[n])]
    assert not unstaged


def test_probe_trace_clock_offset():
    pd = trace_reduce.load(TRACE)
    plane = ts.device_planes(pd, 1)[0]
    lo, hi = ts.clock_offset(ts.module_runs(plane), ts.host_run_bounds(pd),
                             0)
    assert (lo, hi) == (1250182, 1414905)
    # the three runs of one program in the window, attributed as a round
    name = "jit_transpose"
    ops = trace_reduce.device_ops(pd, 1)[0]
    scopes = {trace_reduce.short_name(n): ["fed.local"] for n, _, _ in ops}
    out = ts.summarize(pd, 1, 3, name, scopes)
    (clock,) = out["clock"]
    assert clock["chip"] == 0 and clock["clock_aligned"]
    assert clock["clock_offset_s"] == pytest.approx(1.332543e-3, abs=1e-12)
    assert clock["clock_offset_spread_s"] == pytest.approx(0.164723e-3,
                                                           abs=1e-12)
    by = dict(out["scopes"])
    assert set(by) == {"fed.local", ts.OTHER}
    assert by["fed.local"] == pytest.approx(out["round_busy_s"], rel=1e-9)
    assert out["unstaged_s"] == 0.0
    assert {g[0] for g in out["idle_gaps"]} <= {"bench.step", "no span"}
    with pytest.raises(ValueError, match="2 rounds traced"):
        ts.summarize(pd, 1, 2, name, scopes)


def test_clock_bounds_that_disagree_raise():
    runs = [("p", 100, 200, 1)]
    bounds = {(0, 1): [150, 240]}       # enqueued 50 after, done 40 after
    with pytest.raises(ValueError, match="disagree"):
        ts.clock_offset(runs, bounds, 0)
    assert ts.clock_offset(runs, {(0, 1): [120, 260]}, 0) == (20, 60)
    assert ts.clock_offset(runs, {}, 0) is None


def test_scope_attribution_synthetic():
    hlo = "\n".join([
        "HloModule jit_round_fn, is_scheduled=true",
        "",
        "%body (p: f32[]) -> f32[] {",
        "  ROOT %dot.7 = f32[] dot(f32[] %p, f32[] %p)",
        "}",
        "",
        "ENTRY %main (a: f32[]) -> f32[] {",
        '  %fusion.1 = f32[] fusion(f32[] %a), kind=kLoop, calls=%f1, '
        'metadata={op_name="jit(round_fn)/fed.local/while/body/'
        'transpose(jvp(model.attention))/dot_general"}',
        '  %fusion.2 = f32[] fusion(f32[] %a), kind=kLoop, calls=%f2, '
        'metadata={op_name="jit(round_fn)/fed.local/checkpoint/'
        'lowrank.apply/lowrank.norm_probe/mul"}',
        '  %while.1 = f32[] while(f32[] %a), condition=%cond, body=%body, '
        'metadata={op_name="jit(round_fn)/fed.local/while"}',
        "  %copy.3 = f32[] copy(f32[] %a)",
        '  ROOT %dot.4 = f32[] dot(f32[] %a, f32[] %a), '
        'metadata={op_name="jit(round_fn)/fed.aggregate/dot_general"}',
        "}",
    ])
    module, scopes = ts.hlo_scopes(hlo)
    assert module == "jit_round_fn"
    assert scopes["fusion.1"] == ["fed.local", "model.attention"]
    assert scopes["fusion.2"] == ["fed.local", "lowrank.apply",
                                  "lowrank.norm_probe"]
    assert scopes["dot.7"] == ["fed.local"]         # from its caller
    assert scopes["copy.3"] == []
    ops = [("%while.1 = f32[] while()", 100, 200),
           ("%fusion.1 = f32[] fusion()", 110, 150),
           ("%dot.7 = f32[] dot()", 150, 190),
           ("%fusion.1 = f32[] fusion()", 215, 225),   # another program's
           ("%copy.3 = f32[] copy()", 300, 310),
           ("%dot.4 = f32[] dot()", 310, 400),
           ("%fusion.2 = f32[] fusion()", 900, 950)]   # outside the window
    runs = [("jit_round_fn", 100, 200, 1), ("jit_other", 210, 230, 2),
            ("jit_round_fn", 300, 400, 3), ("jit_round_fn", 900, 1000, 4)]
    secs, n_runs, extra = ts.scope_seconds(ops, runs, scopes,
                                           "jit_round_fn", 50, 550, 10)
    assert n_runs == 2
    assert {k: round(v * 1e9) for k, v in secs.items()} == {
        "fed.local": 20 + 40, "model.attention": 40, ts.OTHER: 10,
        ts.NO_SCOPE: 10, "fed.aggregate": 90}
    assert extra["round_busy_s"] == pytest.approx(200e-9)
    assert extra["unstaged_s"] == pytest.approx(10e-9)


def test_program_text_without_stage_scopes():
    staged = "\n".join([
        "HloModule jit_round_fn", "ENTRY %main () -> f32[] {",
        '  ROOT %dot.4 = f32[] dot(), metadata={op_name="fed.local/dot"}',
        "}"])
    bare = staged.replace(', metadata={op_name="fed.local/dot"}', "")
    other = bare.replace("%dot.4", "%dot.5")
    assert ts.staged_text(staged, None) is staged
    assert ts.staged_text(bare, lambda: staged) is staged
    with pytest.raises(ValueError, match="compile-cache entry"):
        ts.staged_text(bare, lambda: other.replace(
            "dot()", 'dot(), metadata={op_name="fed.local/dot"}'))
    with pytest.raises(ValueError, match="no fed"):
        ts.staged_text(bare, lambda: bare)


def test_round_spans_in_a_cpu_profile(tmp_path):
    engine, batch = tiny_engine(method="fedgalore_minus")
    engine.run_round(batch)                      # compiles
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.round"):
                engine.run_round(batch)
    jax.profiler.stop_trace()
    pd = trace_reduce.load(tmp_path)
    spans = ts.host_spans(pd)
    outer = [(s, e) for n, s, e, _ in spans if n == "bench.round"]
    rounds = sorted((s, e, st["round"]) for n, s, e, st in spans
                    if n == "fed.round")
    assert [r for _, _, r in rounds] == [1, 2] and len(outer) == 2
    for (s, e, _), (bs, be) in zip(rounds, sorted(outer)):
        assert bs <= s <= e <= be
        kids = sorted(n for n, cs, ce, _ in spans
                      if n.startswith("fed.round.") and s <= cs <= ce <= e)
        assert kids == ["fed.round.dispatch", "fed.round.prepare",
                        "fed.round.readback"]
    # No device in the trace: the scope metrics have nothing to read.
    ctx = types.SimpleNamespace(trace_dir=tmp_path, chips=1)
    assert ts.for_run(ctx) is None


def test_trace_without_round_spans_reads_nothing():
    ctx = types.SimpleNamespace(trace_dir=TRACE, chips=1)
    assert ts.for_run(ctx) is None
    assert common.metric_reader("attention_ms.round").read({}, ctx) is None

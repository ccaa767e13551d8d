"""The trace reduction on a small trace recorded on the chip
(bench/traces/probe-v5e.xplane.pb, see README.txt beside it)."""
import pytest

import common
import trace_reduce

TRACE = common.BENCH / "traces" / "probe-v5e.xplane.pb"


class Ctx:
    device_kind = "TPU v5 lite"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE, chips=1)


def test_window_busy_and_idle(summary):
    assert summary["window_s"] == pytest.approx(0.03792469, rel=1e-6)
    assert 0 < summary["busy_s"] < 0.1 * summary["window_s"]
    assert summary["collective_s"] == 0.0
    idle = common.metric_reader("idle_share.round").read(summary, Ctx())
    assert 90.0 < idle < 100.0


def test_kernels_found_with_shapes(summary):
    kinds = {trace_reduce.op_kind(n): n for n in summary["ops"]}
    for k in ("lowrank_linear", "lowrank_linear_batched",
              "galore_precond_step"):
        assert k in kinds
    out, args = trace_reduce.shapes(kinds["lowrank_linear"])
    assert out[0][:2] == ("bf16", [1024, 2816])
    assert [a[1] for a in args] == [[1, 1], [1024, 1024], [1024, 2816],
                                    [1024, 8], [8, 2816]]


def test_rooflines_within_100(summary):
    v = common.metric_reader("lowrank_linear_roofline.round").read(
        summary, Ctx())
    assert 0.0 < v <= 100.0


def test_breakdown(summary):
    b = summary["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    secs = [g[1] for g in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert {g[0] for g in b["idle_gaps"]} <= {"bench.step", "no span"}
    assert sum(s for _, s in b["device_ops"]) <= summary["busy_s"] * 1.001


def test_interval_arithmetic():
    u = trace_reduce._union([(0, 5), (3, 8), (10, 12)])
    assert u == [(0, 8), (10, 12)]
    # collective time 0-10 overlapped by compute 2-4 and 6-7: 7 exposed
    assert trace_reduce._subtract([(0, 10)], [(2, 4), (6, 7)]) == 7
    assert trace_reduce._subtract([(0, 10)], []) == 10
    assert trace_reduce.is_collective("%all-reduce.3 = f32[8] all-reduce(")


def test_self_time_of_nested_ops():
    # a loop 0-100 holding a fusion 10-30 and a conditional 40-90, which
    # holds a kernel 50-70; a separate op 120-130
    evs = [("while", 0, 100), ("fusion", 10, 30), ("cond", 40, 90),
           ("kernel", 50, 70), ("copy", 120, 130)]
    assert trace_reduce._self_times(evs) == [30, 20, 30, 20, 10]

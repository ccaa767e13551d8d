"""The control: the plain reference put in the program's place in the
next precision below the configuration's (float8 for bfloat16) reads
``correct`` false through a run's own comparison, at a size a test run
holds; and the readings that set the limits judge alike."""
import common
import run
from drivers import round as round_driver
from helpers_tiny import ctx_tiny, run_tiny


def test_round_control_run_is_not_correct(monkeypatch):
    drv = common.driver("round")
    real = drv.program_first_rounds

    def control_first_rounds(ctx, arch, traffic, n):
        engine, _, last_s = real(ctx, arch, traffic, n)
        low = drv.reference_readings(ctx, traffic, n, mode="fp8")
        return engine, low, last_s
    monkeypatch.setattr(drv, "program_first_rounds", control_first_rounds)
    monkeypatch.setattr(common, "driver", lambda kind: drv)
    out = run_tiny("round")
    assert not out["correct"], out["checks"]


def test_round_readings_judged_as_a_run():
    ctx = ctx_tiny("round")
    got = round_driver.readings(ctx, faults=("half_batch",))
    limits = ctx.cell["limits"]
    assert run.judge(got["program"], limits)[1]
    assert not run.judge(got["control"], limits)[1]
    assert not run.judge(got["half_batch"], limits)[1]

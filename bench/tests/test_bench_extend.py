"""A configuration, a cell, a driver kind and a per-layer metric are added
by adding files and manifest entries only: a throwaway set of each, in a
copy of the benchmark, runs without an edit to any file that is there."""
import json
import os
import shutil
import subprocess
import sys

import common

DRIVER = '''
def run(ctx):
    ctx.setup_done()
    ctx.read_memory()
    return {"e2e": {"echo_s": float(ctx.cell["echo"]["value"])},
            "attempted": 1, "failed": 0, "checks": {"echo_gap": 0.0}}
'''
METRIC = '''
def read(summary, ctx):
    return summary["busy_s"] * 10.0
'''
SCRIPT = '''
import json, sys, time
sys.path.insert(0, "bench")
import common, run
man = common.manifest()
name = "throwaway.echo"
wl = common.workload(name, man)
cell = common.cell_file(name)
conf = common.config_file(wl["config"], man)
out = run.run_cell(name, cell, conf, man, 2 ** 35, 0.1, False, 1,
                   require_chip=False, t0=time.perf_counter())
ctx = run.Ctx(name, cell, conf, 1, 0.1, False, 1)
e2e = [m["name"] for m in man["end_to_end"]
       if name in m.get("workloads", [name])]
layer = run.per_layer_metrics(man, name, e2e, ctx, {"busy_s": 0.5})
print(json.dumps({"out": out, "layer": layer}))
'''


def test_throwaway_cell_runs_from_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(common.ROOT / "src", root / "src")
    man = common.manifest()
    conf = dict(common.load_json(common.BENCH / "tests" / "data" /
                                 "tiny-qwen.json"),
                name="throwaway", source="https://example.org/throwaway",
                reduced=[])
    (root / "bench" / "configs" / "throwaway.json").write_text(
        json.dumps(conf))
    (root / "bench" / "cells" / "throwaway.echo.json").write_text(
        json.dumps({"driver": "echo", "echo": {"value": 1.5},
                    "limits": {"echo_gap": 0.1}}))
    (root / "bench" / "drivers" / "echo.py").write_text(DRIVER)
    (root / "bench" / "metrics" / "echo_share.layer.py").write_text(METRIC)
    man["configs"].append({"name": "throwaway", "source": conf["source"],
                           "file": "bench/configs/throwaway.json",
                           "reduced": [], "why": "throwaway"})
    man["workloads"].append({"name": "throwaway.echo", "config": "throwaway",
                             "traffic": "echo", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "echo_s", "unit": "s",
                              "better": "lower", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["throwaway.echo"]})
    man["per_layer"].append({"name": "echo_share.layer", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "echo", "moves": "echo_s",
                             "workloads": ["throwaway.echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    assert got["out"]["correct"]
    assert got["out"]["metrics"]["echo_s"]["value"] == 1.5
    assert set(got["out"]["metrics"]) == {"echo_s", "setup_s"}
    assert got["layer"] == {"echo_share.layer": {"value": 5.0, "unit": "%"}}

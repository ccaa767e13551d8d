"""A run with the timed path broken underneath reads ``correct`` false:
once for each fault a round cell can have (bench/faults.py). No cell spans
chips, so the exchange between chips is not among them."""
import os
import subprocess
import sys

import pytest

import common
from faults import planted
from helpers_tiny import run_tiny


@pytest.mark.parametrize("conf", ["tiny-qwen", "tiny-roberta"])
def test_round_sound_run_is_correct(conf):
    out = run_tiny("round", conf)
    assert out["correct"], out["checks"]


def test_round_state_unchanged():
    with planted("unchanged"):
        out = run_tiny("round")
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] > 0.9
    assert out["checks"]["grad_gap"]["value"] > 0.9


def test_round_half_batch():
    with planted("half_batch"):
        out = run_tiny("round")
    assert not out["correct"]


@pytest.mark.parametrize("fault,number", [("loss_altered", "loss_gap"),
                                          ("update_doubled", "grad_gap")])
def test_round_answer_altered(fault, number):
    with planted(fault):
        out = run_tiny("round")
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


def test_second_seed_compiles_nothing(tmp_path):
    """Every program a run builds is found in the compile cache by a run
    with another seed: the seed changes values, never a program. (In a
    process of its own, so that JAX reads the cache directory at start.)"""
    script = (
        "import sys; sys.path[:0] = ['bench', 'bench/tests']\n"
        "import run; from helpers_tiny import run_tiny\n"
        "run_tiny('round', seed=11); before = run.COMPILES[0]\n"
        "ok = run_tiny('round', seed=2 ** 34 + 3)['correct']\n"
        "print(before, run.COMPILES[0], ok)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(common.ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", script], cwd=common.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    before, after, ok = res.stdout.strip().splitlines()[-1].split()
    assert int(before) > 0 and after == before and ok == "True"

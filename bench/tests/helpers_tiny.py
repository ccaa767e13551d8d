"""A whole run of a tiny cell on the CPU, without the harness's look for a
chip: the configurations and cells under ``bench/tests/data``."""
import time

import common
import run

DATA = common.BENCH / "tests" / "data"
MAN = {"end_to_end": [
    {"name": "setup_s", "unit": "s"},
    {"name": "round_s", "unit": "s", "workloads": ["tiny-round"]},
    {"name": "serve_tok_s", "unit": "tokens/s", "workloads": ["tiny-serve"]},
    {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["tiny-serve"]},
    {"name": "tpot_p95_ms", "unit": "ms", "workloads": ["tiny-serve"]}],
    "per_layer": []}


def tiny(kind, conf="tiny-qwen"):
    cell = common.load_json(DATA / f"tiny-{kind}.json")
    return cell, common.load_json(DATA / f"{conf}.json")


def run_tiny(kind, conf="tiny-qwen", seed=2 ** 33 + 5, seconds=0.5):
    cell, c = tiny(kind, conf)
    return run.run_cell(f"tiny-{kind}", cell, c, MAN, seed, seconds, False,
                        1, require_chip=False, t0=time.perf_counter())


def ctx_tiny(kind, conf="tiny-qwen", seed=2 ** 33 + 5):
    cell, c = tiny(kind, conf)
    ctx = run.Ctx(f"tiny-{kind}", cell, c, seed, 0.5, False, 1,
                  t0=time.perf_counter())
    ctx.log = lambda msg: None
    return ctx

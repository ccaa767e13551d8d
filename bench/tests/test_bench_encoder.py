"""The encoder cell (``fed-round-nosync.roberta.cohort64``) from its own
files: driver kind ``round_encoder``, the encoder reference, its operation
count and its readers, at a test's size on the CPU."""
import math

import jax
import numpy as np
import pytest

import common
import run
import trace_scopes
from flops import encoder as flops_encoder
from helpers_tiny import ctx_tiny, run_tiny, tiny

CELL = "fed-round-nosync.roberta.cohort64"


def test_cell_is_the_tiny_cells_driver_at_its_own_sizes():
    cell = common.cell_file(CELL)
    small, conf = tiny("round-encoder", "tiny-roberta-encoder")
    assert cell["driver"] == small["driver"] == "round_encoder"
    assert set(cell["fed"]) == set(small["fed"])
    assert set(cell["limits"]) == set(small["limits"])
    fed = cell["fed"]
    assert (fed["clients"], fed["local_steps"], fed["batch"],
            fed["seq_len"], fed["rank"]) == (64, 2, 8, 128, 8)
    assert fed["clients"] % fed["client_chunk"] == 0
    full = common.config_file("roberta-base")["arch"]
    assert set(conf["arch"]) == set(full)
    assert full["n_classes"] == fed["classes"]


def test_traffic_has_s_first_and_one_class_per_row():
    ctx = ctx_tiny("round-encoder", "tiny-roberta-encoder")
    drv = common.driver("round_encoder")
    r = drv.traffic_of(ctx.cell, ctx.conf, ctx.seed).round(0)
    fed = ctx.cell["fed"]
    lead = (fed["clients"], fed["local_steps"], fed["batch"])
    assert r["tokens"].shape == lead + (fed["seq_len"],)
    assert r["labels"].shape == lead
    assert np.all(r["tokens"][..., 0] == 0)
    assert r["labels"].min() >= 0 and r["labels"].max() < fed["classes"]


def test_sound_run_is_correct():
    out = run_tiny("round-encoder", "tiny-roberta-encoder")
    assert out["correct"], out["checks"]


def test_control_and_faults_are_not_correct():
    ctx = ctx_tiny("round-encoder", "tiny-roberta-encoder")
    drv = common.driver("round_encoder")
    got = drv.readings(ctx, faults=("unchanged", "half_batch",
                                    "update_doubled"))
    limits = ctx.cell["limits"]
    assert run.judge(got["program"], limits)[1], got["program"]
    for who in ("control", "unchanged", "half_batch", "update_doubled"):
        assert not run.judge(got[who], limits)[1], (who, got[who])


def test_reference_matches_the_program_in_float32():
    """The benchmark's encoder reference against the program's forward at
    the tiny configuration in float32: the same function of the weights."""
    import dataclasses
    from reference import encoder as ref
    _, conf = tiny("round-encoder", "tiny-roberta-encoder")
    arch = dataclasses.replace(common.arch_config(conf), dtype="float32",
                               remat=False)
    from repro.models import model as model_lib
    params = common.make_weights(arch, 5)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (3, 16), 0, 300)
    want = ref.forward(params, conf["arch"], tokens)
    got, _ = model_lib.forward(params, arch, tokens)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        np.max(np.abs(want))), rtol=0)


def test_round_program_carries_the_post_norm_scope():
    ctx = ctx_tiny("round-encoder", "tiny-roberta-encoder")
    _, scopes = trace_scopes.hlo_scopes(trace_scopes.round_program_text(ctx))
    found = {s for path in scopes.values() for s in path}
    assert {"model.post_norm", "model.attention", "model.head",
            "lowrank.apply", "lowrank.norm_probe"} <= found


def test_required_operations_hand_count():
    """Two layers, d 4, two heads, d_ff 8, 3 classes; 2 clients x 1 step x
    1 sequence of 3 tokens, rank 2."""
    arch = {"d_model": 4, "n_heads": 2, "n_kv_heads": 2, "d_ff": 8,
            "vocab_size": 50, "n_layers": 2, "mlp_kind": "plain",
            "n_classes": 3}
    fed = {"clients": 2, "local_steps": 1, "batch": 1, "seq_len": 3,
           "rank": 2}
    seqs, tokens = 2, 6
    n_tgt = 2 * (4 * 16 + 2 * 32)                # wq wk wv wo, w_up w_down
    rc = 2 * (4 * 2 * 8 + 2 * 2 * 12)            # r(m + n) per layer
    first_qkv = 3 * 16
    per_tok = (2 * n_tgt + 2 * rc) + (2 * (n_tgt - first_qkv) + 2 * rc) \
        + 2 * rc
    attn = 12 * 4 * 9 * 2 * seqs                 # all 9 pairs at L = 3
    head = 4 * (4 * 4 + 4 * 3) * seqs            # dense + out_proj at <s>
    assert flops_encoder.round_required(arch, fed) == \
        tokens * per_tok + attn + head


def test_mfu_encoder_reader():
    cell = common.cell_file(CELL)
    conf = common.config_file("roberta-base")
    ctx = run.Ctx(CELL, cell, conf, 1, 10.0, True, 1)
    ctx.device_kind = "TPU v5 lite"
    reader = common.metric_reader("mfu_encoder.round")
    assert reader.read({"work": None, "window_s": 1.0}, ctx) is None
    need = flops_encoder.round_required(conf["arch"], cell["fed"])
    peak = common.peaks("TPU v5 lite")["bf16_flops"]
    got = reader.read({"work": {"rounds": 3}, "window_s": 4.0}, ctx)
    assert got == pytest.approx(100.0 * 3 * need / (4.0 * peak))
    assert 4e13 < need < 5e13 and math.isfinite(got)

"""End-to-end behaviour tests for the full system."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.core.fed import FedConfig, FedEngine
from repro.data import FederatedBatcher, seq_classification
from repro.launch.steps import galore_target_fn
from repro.models import model as M


def _run_federation(method, alpha, rounds=8, seed=0):
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    key = jax.random.PRNGKey(seed)
    params = M.init_params(key, cfg)
    task = seq_classification(512, 4, 16, cfg.vocab_size, seed=seed)
    batcher = FederatedBatcher(task, n_clients=4, batch_size=8, alpha=alpha,
                               seed=seed)

    def loss(p, batch):
        return M.loss_fn(p, cfg, batch)

    eng = FedEngine(FedConfig(method=method, rank=4, lr=3e-2, local_steps=8,
                              seed=seed),
                    loss, params, target_fn=galore_target_fn(cfg))
    for _ in range(rounds):
        batches = {k: jnp.asarray(v)
                   for k, v in batcher.round_batches(8).items()}
        eng.run_round(batches)
    gp = eng.global_params()
    eval_b = batcher.eval_batch(128)
    logits, _ = M.forward(gp, cfg, jnp.asarray(eval_b["tokens"]))
    acc = float((np.asarray(logits[:, -1]).argmax(-1)
                 == eval_b["labels"][:, -1]).mean())
    return acc


def test_fedgalore_learns_iid():
    # The paper's target modules freeze the (tied) output embedding, so the
    # 2-layer smoke model must align hidden states with frozen class rows —
    # chance over the full vocab is ~0.002; ≥0.3 on 4 classes is clear
    # learning within the 64-step budget.
    acc = _run_federation("fedgalore", alpha=None)
    assert acc > 0.3, acc


def test_fedgalore_learns_noniid():
    acc = _run_federation("fedgalore", alpha=0.5)
    assert acc > 0.2, acc


@pytest.fixture
def no_cache_dir(monkeypatch, tmp_path):
    """A launcher's main() points JAX's persistent compilation cache at the
    checkout unless JAX_COMPILATION_CACHE_DIR is set; setting it keeps the
    test process's config untouched."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    before = jax.config.jax_compilation_cache_dir
    yield
    assert jax.config.jax_compilation_cache_dir == before


def test_train_launcher_cli(tmp_path, no_cache_dir):
    out = tmp_path / "hist.json"
    from repro.launch import train as train_mod
    res = train_mod.main([
        "--arch", "qwen1.5-0.5b", "--smoke", "--method", "fedgalore",
        "--rounds", "2", "--clients", "3", "--local-steps", "2",
        "--batch", "4", "--seq", "16", "--examples", "256",
        "--alpha", "0.5", "--out", str(out)])
    hist = res["history"]
    assert len(hist) == 2
    assert all(np.isfinite(h["val_loss"]) for h in hist)
    assert json.loads(out.read_text()) == hist
    lowered = res["engine"].lower_round(res["last_batches"])
    assert "func.func public @main" in lowered.as_text()


def test_chunked_evaluation_matches_whole_batch():
    """launch.train.evaluate runs the batch in EVAL_ROWS-row chunks (the
    full-vocab logits of a whole batch do not fit a chip); its loss and
    accuracy equal the whole-batch computation."""
    from repro.launch import train as train_mod
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    task = seq_classification(64, 4, 16, cfg.vocab_size, seed=0)
    n = 3 * train_mod.EVAL_ROWS + 5                   # a ragged last chunk
    batch = {"tokens": jnp.asarray(task.tokens[:n]),
             "labels": jnp.asarray(task.labels[:n])}
    loss, acc = train_mod.evaluate(params, cfg, batch)
    logits, _ = M.forward(params, cfg, batch["tokens"])
    want_acc = float(jnp.mean(jnp.argmax(logits[:, -1], -1) ==
                              batch["labels"][:, -1]))
    assert loss == pytest.approx(float(M.loss_fn(params, cfg, batch)),
                                 rel=1e-5)
    assert acc == want_acc


def test_chip_smoke_refuses_a_host_without_tpu():
    """chip_smoke.py fails (SystemExit) when JAX's device is not a TPU."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.device_check(1)


def test_serve_launcher_cli(capsys, no_cache_dir):
    from repro.launch import serve as serve_mod
    res = serve_mod.main(["--arch", "rwkv6-1.6b", "--smoke", "--batch", "2",
                          "--prompt-len", "8", "--new-tokens", "4"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["tokens_per_sec"] > 0
    assert len(out["sample_row"]) == 4
    assert sorted(res["outputs"]) == [0, 1]
    assert res["outputs"][0] == out["sample_row"]


def test_generate_deterministic_greedy():
    from repro.launch.serve import generate
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    a = generate(params, cfg, prompts, 4, cache_len=16)
    b = generate(params, cfg, prompts, 4, cache_len=16)
    assert jnp.array_equal(a, b)


def test_checkpoint_resume_consistency(tmp_path):
    from repro.checkpoint import restore, save
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    save(str(tmp_path), 0, params)
    params2 = restore(str(tmp_path), 0, params)
    toks = jnp.zeros((1, 8), jnp.int32)
    a, _ = M.forward(params, cfg, toks)
    b, _ = M.forward(params2, cfg, toks)
    assert jnp.allclose(a, b)

"""Sharding rules + small-mesh lowering (the dry-run's little sibling).

Rule resolution is tested against an AbstractMesh (no devices needed); the
numerical sharded-vs-unsharded equivalence runs in a subprocess with
``--xla_force_host_platform_device_count`` so the main pytest process keeps
its single CPU device (per the dry-run isolation requirement).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import model as M
from repro.sharding.rules import ShardingRules


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.AbstractMesh((4, 2), ("data", "model"))


def test_param_rules(mesh):
    rules = ShardingRules(mesh, fsdp=True)
    assert rules.param_rule("blocks/0/attn/wq") == "col"
    assert rules.param_rule("blocks/0/attn/wo") == "row"
    assert rules.param_rule("blocks/0/moe/w_gate") == "exp_col"
    assert rules.param_rule("blocks/0/moe/router") == "repl"
    assert rules.param_rule("embed/w") == "emb"
    assert rules.param_rule("blocks/0/norm1/scale") == "repl"
    assert rules.param_rule("blocks/0/cmix/wv") == "row"
    assert rules.param_rule("blocks/0/tmix/wk") == "col"
    assert rules.param_rule("blocks/0/mamba/in_proj") == "col"
    assert rules.param_rule("blocks/0/mamba/x_proj") == "row"


def test_specs_divisibility_guard(mesh):
    rules = ShardingRules(mesh, fsdp=True)
    spec = rules.param_spec("blocks/0/attn/wq", (3, 7, 6))
    for dim, axes in zip((3, 7, 6), list(spec) + [None] * 3):
        if axes is not None:
            size = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                size *= mesh.shape[a]
            assert dim % size == 0


def test_col_row_assignment(mesh):
    rules = ShardingRules(mesh, fsdp=True)
    spec = rules.param_spec("blocks/0/attn/wq", (6, 8, 8))
    assert spec == jax.sharding.PartitionSpec(None, "data", "model")
    spec = rules.param_spec("blocks/0/attn/wo", (6, 8, 8))
    assert spec == jax.sharding.PartitionSpec(None, "model", "data")
    # fsdp off: data axis never appears on params
    rules_tp = ShardingRules(mesh, fsdp=False)
    spec = rules_tp.param_spec("blocks/0/attn/wq", (6, 8, 8))
    assert spec == jax.sharding.PartitionSpec(None, None, "model")


def test_params_shardings_tree(mesh):
    cfg = smoke_variant(get_config("granite-moe-1b-a400m"))
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    sh = ShardingRules(mesh).params_shardings(params)
    assert len(jax.tree_util.tree_leaves(sh)) == \
        len(jax.tree_util.tree_leaves(params))


def test_decode_state_shardings(mesh):
    cfg = smoke_variant(get_config("qwen1.5-0.5b"))
    state = jax.eval_shape(lambda: M.init_decode_state(cfg, 4, 32))
    sh = ShardingRules(mesh).decode_state_shardings(state)
    assert jax.tree_util.tree_leaves(sh)


_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp
from repro.configs import get_config, smoke_variant
from repro.models import model as M
from repro.sharding.rules import ShardingRules

arch = sys.argv[1]
cfg = smoke_variant(get_config(arch))
params = M.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                          cfg.vocab_size)
ref, _ = M.forward(params, cfg, toks)
mesh = jax.make_mesh((4, 2), ("data", "model"))
rules = ShardingRules(mesh)
p_sh = jax.device_put(params, rules.params_shardings(params))
t_sh = jax.device_put(toks, rules.data_shardings(toks))
with jax.set_mesh(rules.mesh):
    out, _ = jax.jit(lambda p, t: M.forward(p, cfg, t))(p_sh, t_sh)
err = float(jnp.max(jnp.abs(ref - out)))
assert err < 2e-2, (arch, err)
print(arch, "ok", err)
"""


@pytest.mark.parametrize("arch", [
    "qwen1.5-0.5b",
    "granite-moe-1b-a400m",
    "rwkv6-1.6b",
])
def test_sharded_forward_matches_single_device(arch):
    """Numerical equivalence under SPMD sharding (subprocess, 8 fake devices,
    one arch per process so one arch's failure doesn't mask the others)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _SUBPROC_SCRIPT, arch], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr


_ROUND_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
sys.path.insert(0, sys.argv[1])
import chip_smoke
from repro.configs import get_config, smoke_variant
chip_smoke.peak_bytes = lambda device: 0      # the CPU reports no stats
chip_smoke.sharded_phase(jax.devices(),
                         smoke_variant(get_config("qwen1.5-0.5b")), seq=32)
"""


def test_sharded_round_matches_one_device():
    """chip_smoke.py --chips 4's comparison on 4 fake CPU devices: a
    ShardedFederation round on a (data=4, model=1) mesh matches the same
    round on one device within its bf16 tolerance."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _ROUND_SCRIPT, root], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded vs one-device global param max|Δ|" in r.stdout

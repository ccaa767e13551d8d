"""Operation and byte counts against hand sums at toy shapes."""
import pytest

import flops

TOY = {"d_model": 8, "n_heads": 2, "n_kv_heads": 2, "d_ff": 12,
       "vocab_size": 50, "n_layers": 2, "mlp_kind": "glu"}


def test_targets_and_params():
    names = [n for n, _, _ in flops.targets(TOY)]
    assert names == ["attn/wk", "attn/wo", "attn/wq", "attn/wv",
                     "mlp/w_down", "mlp/w_gate", "mlp/w_up"]
    # 4 attention 8x8 + 3 MLP 8x12, two layers
    assert flops.target_params(TOY) == 2 * (4 * 64 + 3 * 96)
    plain = dict(TOY, mlp_kind="plain")
    assert flops.target_params(plain) == 2 * (4 * 64 + 2 * 96)


def test_lowrank_linear_counts():
    f, b = flops.lowrank_linear(t=3, m=4, n=5, r=2)
    assert f == 2 * 3 * 4 * 5 + 2 * 3 * 2 * (4 + 5)
    assert b == 2 * 4 * 5 + 2 * 3 * 4 + 2 * 3 * 5 + 4 * 2 * (4 + 5)
    f2, b2 = flops.lowrank_linear(t=3, m=4, n=5, r=2, clients=2)
    assert f2 == 2 * f and b2 == b + (b - 2 * 4 * 5)


def test_round_required_hand_sum():
    fed = {"clients": 2, "local_steps": 1, "batch": 1, "seq_len": 3,
           "rank": 2}
    seqs, tokens = 2, 6
    n_tgt = 2 * (4 * 64 + 3 * 96)
    rc = 2 * (4 * 2 * 16 + 3 * 2 * 20)          # per layer r(m+n), 2 layers
    first_qkv = 3 * 64
    per_tok = (2 * n_tgt + 2 * rc) + (2 * (n_tgt - first_qkv) + 2 * rc) \
        + 2 * rc
    attn = 12 * 8 * 6 * 2 * seqs               # 6 causal pairs at L=3
    head = 4 * 50 * 8 * seqs
    assert flops.round_required(TOY, fed) == tokens * per_tok + attn + head


def test_min_time_takes_the_binding_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.min_time(1000.0, 50.0, peak) == pytest.approx(10.0)
    assert flops.min_time(100.0, 50.0, peak) == pytest.approx(5.0)

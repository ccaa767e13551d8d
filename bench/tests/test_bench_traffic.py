"""The seeded generators: same seed, same inputs; other seeds, the same
amount of work."""
import numpy as np

from traffic.federated import DirichletSeqClassification

ROUND = dict(vocab=300, clients=6, local_steps=2, batch=2, seq_len=16,
             classes=4, alpha=0.5)


def test_round_traffic_deterministic():
    a = DirichletSeqClassification(seed=2 ** 40 + 7, **ROUND)
    b = DirichletSeqClassification(seed=2 ** 40 + 7, **ROUND)
    for k in (0, 3):
        ra, rb = a.round(k), b.round(k)
        for key in ra:
            np.testing.assert_array_equal(ra[key], rb[key])


def test_round_traffic_shapes_labels_and_fresh_rows():
    t = DirichletSeqClassification(seed=11, **ROUND)
    r0, r1 = t.round(0), t.round(1)
    assert r0["tokens"].shape == (6, 2, 2, 16)
    assert r0["tokens"].max() < 300 - 4
    lab = r0["labels"]
    assert (lab[..., :-1] == -1).all() and (lab[..., -1] >= 296).all()
    rows = np.concatenate([r0["tokens"].reshape(-1, 16),
                           r1["tokens"].reshape(-1, 16)])
    assert len({tuple(x) for x in rows}) == len(rows)


def test_round_traffic_same_work_across_seeds():
    a = DirichletSeqClassification(seed=3, **ROUND).round(0)
    b = DirichletSeqClassification(seed=2 ** 33 + 1, **ROUND).round(0)
    for key in a:
        assert a[key].shape == b[key].shape and a[key].dtype == b[key].dtype
    assert ((a["labels"] >= 0).sum(-1) == 1).all()
    assert ((b["labels"] >= 0).sum(-1) == 1).all()

"""Seeded federated round traffic: class-conditioned token sequences with a
Dirichlet(α) label skew across clients.

The semantics are those of the repository's ``data/synthetic.py``
(``seq_classification``: class-conditioned unigram tokens, the class token
as the only label, at the last position) and ``data/partition.py``
(``dirichlet_label_partition``: each class is split across clients by a
Dir(α·1_C) draw), kept here so that the yardstick cannot move with the
program. The data set is a stream, not a finite shard: a client's class
mix is its share of every class, P(c | i) ∝ props[c, i], and every round
draws fresh rows, so no row repeats across the rounds of a run.

Every seed gives the same shapes and the same number of rows; the seed
changes only the values.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class DirichletSeqClassification:
    def __init__(self, *, seed: int, vocab: int, clients: int,
                 local_steps: int, batch: int, seq_len: int,
                 classes: int = 8, alpha: float = 0.5,
                 signal: float = 3.0):
        self.seed = int(seed)
        self.vocab, self.clients = int(vocab), int(clients)
        self.local_steps, self.batch = int(local_steps), int(batch)
        self.seq_len, self.classes = int(seq_len), int(classes)
        rng = np.random.default_rng([self.seed, 0])
        content = self.vocab - self.classes     # last ids are the labels
        self.content = content
        logits = rng.normal(size=(self.classes, content))
        boost = rng.integers(0, content, (self.classes, max(2, content // 16)))
        for c in range(self.classes):
            logits[c, boost[c]] += signal
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        self.cdf = np.cumsum(probs, axis=-1)
        self.cdf[:, -1] = 1.0
        # Dir(α·1_C) split of every class across the clients
        props = rng.dirichlet(alpha * np.ones(self.clients),
                              size=self.classes)          # (classes, C)
        mix = props.T + 1e-12                              # (C, classes)
        self.client_mix = mix / mix.sum(-1, keepdims=True)

    def round(self, index: int) -> Dict[str, np.ndarray]:
        """Batches of round ``index``: tokens and labels with leading
        (clients, local steps, batch) axes."""
        rng = np.random.default_rng([self.seed, 1, int(index)])
        c, t, b, l = self.clients, self.local_steps, self.batch, self.seq_len
        rows = t * b
        cls = np.stack([rng.choice(self.classes, size=rows,
                                   p=self.client_mix[i]) for i in range(c)])
        u = rng.random((c, rows, l))
        tokens = np.empty((c, rows, l), np.int32)
        for k in range(self.classes):
            sel = cls == k
            if sel.any():
                tokens[sel] = np.searchsorted(self.cdf[k], u[sel],
                                              side="right")
        np.minimum(tokens, self.content - 1, out=tokens)
        labels = np.full((c, rows, l), -1, np.int32)
        labels[..., -1] = self.content + cls
        return {"tokens": tokens.reshape(c, t, b, l),
                "labels": labels.reshape(c, t, b, l)}

"""Plain reference of the federated GaLore round without state sync
(every client starts each round from fresh moments), in float32.

For each round k and each client, T local steps on its own batches:

1. the dense gradient of the loss at the client's weights W_k + D_i;
2. global-norm clipping of all target gradients together to ``clip``;
3. the projector: at round 0, step 0, a randomized SVD of the client's
   own clipped gradient (Halko et al.: a Gaussian sketch of width r + 8,
   one power iteration, QR, and the top r right singular vectors of the
   small factor); at every later round, the seeded random orthonormal
   basis that every client rebuilds from the broadcast seed s_k = seed + k
   (QR of a Gaussian, column signs fixed by R's diagonal). A block of
   shape (m, n) projects on the right (basis n x r) when m >= n, on the
   left (m x r) otherwise;
4. Adam on the projected gradient with bias correction at the global step
   count k·T + t + 1, lifted back through the basis, times the learning
   rate, subtracted from D_i.

The server averages: W_{k+1} = W_k + sum_i w_i D_i, kept in float32.

The random draws are keyed as the broadcast-a-seed protocol keys them:
key(s, refresh, block) = fold_in(fold_in(PRNGKey(s), refresh), block),
one further fold_in per layer of a stacked block, where ``block`` counts
the target matrices in tree order.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import model as ref_model

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def block_keys(seed, refresh, block, layers):
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    key = jax.random.fold_in(key, jnp.asarray(refresh, jnp.uint32))
    key = jax.random.fold_in(key, block)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(layers))


def random_basis(key, dim, rank):
    q, r = jnp.linalg.qr(jax.random.normal(key, (dim, rank), jnp.float32))
    s = jnp.sign(jnp.diagonal(r))
    return q * jnp.where(s == 0, 1.0, s)[None, :]


def rsvd_basis(g, rank, right: bool, key, oversample: int = 8):
    if not right:
        g = g.T
    m, n = g.shape
    k = min(rank + oversample, min(m, n))
    omega = jax.random.normal(key, (m, k), jnp.float32)
    y = _mm(g.T, omega)
    y = _mm(g.T, _mm(g, y))
    q, _ = jnp.linalg.qr(y)
    _, _, vt = jnp.linalg.svd(_mm(g, q), full_matrices=False)
    return _mm(q, vt[:rank].T)


def project(g, basis, right: bool):
    return _mm(g, basis) if right else _mm(basis.T, g)


def lift(u, basis, right: bool):
    return _mm(u, basis.T) if right else _mm(basis, u)


class Leaf:
    def __init__(self, name, block_id, shape, rank):
        self.name, self.block_id = name, block_id
        self.layers, self.m, self.n = shape
        self.right = self.m >= self.n
        self.dim = self.n if self.right else self.m
        self.rank = min(rank, self.m, self.n)


def leaves_of(params, rank) -> List[Leaf]:
    blk = params["blocks"][0]
    out = []
    for i, name in enumerate(ref_model.target_names(params)):
        g, n = name.split("/")
        out.append(Leaf(name, i, blk[g][n].shape, rank))
    return out


def reference_rounds(params, arch: Dict, fed: Dict, rounds: List[Dict],
                     grad_round: int = 1, mode: str = "f32") -> Dict:
    """Run ``len(rounds)`` rounds from ``params``. Returns the local losses
    (rounds, clients, T) and the per-layer Frobenius norms, per target
    name, of round ``grad_round``'s global change and of the change over
    all the rounds."""
    leaves = leaves_of(params, fed["rank"])
    blk = params["blocks"][0]
    w0 = {lf.name: blk[lf.name.split("/")[0]][lf.name.split("/")[1]]
          for lf in leaves}
    b1, b2, eps = fed["b1"], fed["b2"], fed["eps"]
    lr, clip, t_steps = fed["lr"], fed["clip_norm"], fed["local_steps"]

    @jax.jit
    def grad_step(params, w, d, tokens, labels):
        eff = {k: w[k] + d[k] for k in w}
        return jax.value_and_grad(
            lambda e: ref_model.loss(params, arch, tokens, labels, mode, e))(
                eff)

    @jax.jit
    def clip_grads(g):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        c = jnp.minimum(1.0, clip / (gn + 1e-12))
        return {k: x * c for k, x in g.items()}

    def bases_random(k):
        out = {}
        for lf in leaves:
            keys = block_keys(fed["seed"] + k, k, lf.block_id, lf.layers)
            out[lf.name] = jax.vmap(
                lambda kk: random_basis(kk, lf.dim, lf.rank))(keys)
        return out

    def bases_rsvd(g):
        out = {}
        for lf in leaves:
            keys = block_keys(fed["seed"], 0, lf.block_id, lf.layers)
            out[lf.name] = jax.vmap(
                lambda gg, kk: rsvd_basis(gg, lf.rank, lf.right, kk))(
                    g[lf.name], keys)
        return out

    @jax.jit
    def adam(g, bases, m, v, d, count):
        c1 = 1.0 - b1 ** count
        c2 = 1.0 - b2 ** count
        new_m, new_v, new_d = {}, {}, {}
        for lf in leaves:
            k = lf.name
            gt = jax.vmap(lambda gg, bb: project(gg, bb, lf.right))(
                g[k], bases[k])
            new_m[k] = b1 * m[k] + (1 - b1) * gt
            new_v[k] = b2 * v[k] + (1 - b2) * gt * gt
            u = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + eps)
            new_d[k] = d[k] - lr * jax.vmap(
                lambda uu, bb: lift(uu, bb, lf.right))(u, bases[k])
        return new_m, new_v, new_d

    def proj_zeros(lf):
        shape = ((lf.layers, lf.m, lf.rank) if lf.right
                 else (lf.layers, lf.rank, lf.n))
        return jnp.zeros(shape, jnp.float32)

    w = {n: x.astype(jnp.float32) for n, x in w0.items()}
    losses, norm_grad = [], None
    for k, rb in enumerate(rounds):
        tokens, labels = rb["tokens"], rb["labels"]
        clients = tokens.shape[0]
        wts = np.full(clients, 1.0 / clients)
        shared = bases_random(k) if k > 0 else None
        acc = {n: jnp.zeros_like(x) for n, x in w.items()}
        round_losses = []
        for c in range(clients):
            d = {n: jnp.zeros_like(x) for n, x in w.items()}
            m = {lf.name: proj_zeros(lf) for lf in leaves}
            v = {lf.name: proj_zeros(lf) for lf in leaves}
            bases = shared
            client_losses = []
            for t in range(t_steps):
                loss_v, g = grad_step(params, w, d,
                                      jnp.asarray(tokens[c, t]),
                                      jnp.asarray(labels[c, t]))
                g = clip_grads(g)
                if bases is None:
                    bases = bases_rsvd(g)
                m, v, d = adam(g, bases, m, v, d,
                               jnp.float32(k * t_steps + t + 1))
                client_losses.append(float(loss_v))
            round_losses.append(client_losses)
            acc = {n: acc[n] + wts[c] * d[n] for n in acc}
        before, w = w, {n: w[n] + acc[n] for n in w}
        losses.append(round_losses)
        if k == grad_round:
            norm_grad = change_norms(w, before)
    return {"losses": np.asarray(losses), "norm_grad": norm_grad,
            "norm_last": change_norms(w, w0)}


def change_norms(w, w0) -> Dict[str, np.ndarray]:
    """Per-layer Frobenius norm of w - w0 for every stacked target."""
    return {k: np.asarray(jnp.sqrt(jnp.sum(
        (w[k].astype(jnp.float32) - w0[k].astype(jnp.float32)) ** 2,
        axis=(1, 2)))) for k in w}

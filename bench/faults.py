"""Faults planted underneath the timed path, to show that a run with the
path broken reads ``correct`` false. Each is a context manager that
patches the program's round entry for the time of one run:

- ``unchanged``: the round runs, but the global state it returns is
  thrown away (a step that returns its state unchanged);
- ``half_batch``: every client's batches lose their second half, so each
  local step's loss and gradient are the mean over the rest;
- ``loss_altered``: one reported local-step loss is moved by 0.1 where the
  round produces it;
- ``update_doubled``: the round's answer, its new global weights, altered
  where the round produces it: the first stacked target's first layer moves
  by twice the round's change.

Used by ``bench/tests/test_bench_faults.py`` at a test's size and by
``bench/calibrate.py readings --faults`` at a cell's own size.
"""
from __future__ import annotations

import contextlib

import common

NAMES = ("unchanged", "half_batch", "loss_altered", "update_doubled")


def _round_entry():
    common.program_path()
    from repro.core.fed import FedEngine
    return FedEngine


def _unchanged(orig):
    def run_round(self, batches, *a, **k):
        before = self.global_trainable
        res = orig(self, batches, *a, **k)
        self.global_trainable = before
        return res
    return run_round


def _half_batch(orig):
    def run_round(self, batches, *a, **k):
        b = batches["tokens"].shape[2] // 2
        return orig(self, {n: v[:, :, :b] for n, v in batches.items()},
                    *a, **k)
    return run_round


def _loss_altered(orig):
    def run_round(self, batches, *a, **k):
        res = orig(self, batches, *a, **k)
        res["local_loss"] = res["local_loss"].at[0, 0].add(0.1)
        return res
    return run_round


def _update_doubled(orig):
    def run_round(self, batches, *a, **k):
        import jax
        before = jax.tree_util.tree_leaves(self.global_trainable)
        res = orig(self, batches, *a, **k)
        after, treedef = jax.tree_util.tree_flatten(self.global_trainable)
        i = next(j for j, x in enumerate(after) if x.ndim == 3)
        w, w0 = after[i][0].astype("float32"), before[i][0].astype("float32")
        after[i] = after[i].at[0].set((2 * w - w0).astype(after[i].dtype))
        self.global_trainable = treedef.unflatten(after)
        return res
    return run_round


@contextlib.contextmanager
def planted(name: str):
    """Patch the round entry with fault ``name`` inside the block."""
    engine = _round_entry()
    make = {"unchanged": _unchanged, "half_batch": _half_batch,
            "loss_altered": _loss_altered,
            "update_doubled": _update_doubled}[name]
    orig = engine.run_round
    engine.run_round = make(orig)
    try:
        yield
    finally:
        engine.run_round = orig

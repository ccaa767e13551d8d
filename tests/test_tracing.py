"""The compile clock (``launch/cache.compile_seconds``): JAX's trace, lower
and backend spans, counted once where they nest, and cut at a moment."""
import time

import jax
import jax.numpy as jnp

from repro.launch import cache


def test_compile_seconds_union_and_cut(monkeypatch):
    # (stage, start, end, perf_counter when it ended): an inner trace ends
    # first, inside the outer one; lowering follows; the backend later
    monkeypatch.setattr(cache, "_SPANS", [
        ("trace", 2.0, 5.0, 1.0), ("trace", 0.0, 10.0, 2.0),
        ("lower", 10.0, 12.0, 3.0), ("backend", 20.0, 30.0, 9.0)])
    assert cache.compile_seconds(("trace",)) == 10.0
    assert cache.compile_seconds(("trace", "lower")) == 12.0
    assert cache.compile_seconds() == 22.0
    assert cache.compile_seconds(("trace",), until=1.5) == 3.0
    assert cache.compile_seconds(until=3.0) == 12.0


def test_compile_seconds_counts_a_new_program(monkeypatch, tmp_path):
    # an explicit cache directory keeps use_compile_cache off the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache.use_compile_cache()
    before = time.perf_counter()
    seen = {st: cache.compile_seconds((st,)) for st in cache.STAGES.values()}
    base = cache.compile_seconds()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    outer(jnp.arange(7.0)).block_until_ready()
    for st, was in seen.items():
        assert cache.compile_seconds((st,)) > was, st
    assert cache.compile_seconds(until=before) == base
    assert cache.compile_seconds() > base

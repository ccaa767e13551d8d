"""JAX's persistent compilation cache for the entry points, and the time
spent getting programs ready.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here changes it. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a cache entry is found by.
Call :func:`use_compile_cache` from an entry point's ``main()`` before its
first JAX operation, never at import.

From that call on, :func:`compile_seconds` reads how long the process spent
tracing functions to jaxprs, lowering them to MLIR, and in the backend's
compile-or-load (a compile-cache hit is timed there too), from the spans
JAX reports to ``jax.monitoring``.
"""
from __future__ import annotations

import os
import pathlib
import time
from typing import Iterable, Optional

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend"}
# (stage, start, end, time.perf_counter() when it ended). JAX's listeners
# are per process, and so is this record.
_SPANS: list = []
_listening = False


def _record(event: str, start: float, end: float, **_) -> None:
    stage = STAGES.get(event)
    if stage is not None:
        _SPANS.append((stage, start, end, time.perf_counter()))


def use_compile_cache() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_time_span_listener(_record)
        _listening = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))


def compile_seconds(stages: Iterable[str] = ("trace", "lower", "backend"),
                    until: Optional[float] = None) -> float:
    """Wall time in the given stages since :func:`use_compile_cache` was
    first called: the length of the union of their spans, so a function
    traced inside another's trace counts once. ``until`` (a
    ``time.perf_counter()`` reading) keeps the spans that had ended by
    then."""
    stages = set(stages)
    spans = sorted((s, e) for st, s, e, t in _SPANS
                   if st in stages and (until is None or t <= until))
    total, reach = 0.0, float("-inf")
    for s, e in spans:
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total

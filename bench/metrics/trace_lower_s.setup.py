"""trace_lower_s.setup: host seconds of set-up spent tracing functions to
jaxprs and lowering them to MLIR (the union of JAX's spans, so nested traces
count once), from the program's compile clock
(``repro.launch.cache.compile_seconds``)."""


def read(summary, ctx):
    try:
        from repro.launch.cache import compile_seconds
    except ImportError:          # a program without the compile clock
        return None
    return compile_seconds(("trace", "lower"), until=ctx.t0 + ctx.setup_s)

"""backend_load_s.setup: host seconds of set-up spent in the backend's
compile-or-load of programs (a compile-cache hit is timed here too), from
the program's compile clock (``repro.launch.cache.compile_seconds``)."""


def read(summary, ctx):
    try:
        from repro.launch.cache import compile_seconds
    except ImportError:          # a program without the compile clock
        return None
    return compile_seconds(("backend",), until=ctx.t0 + ctx.setup_s)

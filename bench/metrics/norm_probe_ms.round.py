"""norm_probe_ms.round: device self time of the ``lowrank.norm_probe`` scope
(the token Grams of the exact clip-norm probe) per traced round, in ms
(bench/trace_scopes.py)."""
import trace_scopes


def read(summary, ctx):
    return trace_scopes.ms_per_round(ctx, "lowrank.norm_probe")

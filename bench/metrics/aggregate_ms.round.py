"""aggregate_ms.round: device self time of the ``fed.aggregate`` scope (the
server's aggregation) per traced round, in ms (bench/trace_scopes.py)."""
import trace_scopes


def read(summary, ctx):
    return trace_scopes.ms_per_round(ctx, "fed.aggregate")

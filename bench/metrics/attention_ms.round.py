"""attention_ms.round: device self time of the ``model.attention`` scope
(scores, softmax and context, forward and backward) per traced round, in ms
(bench/trace_scopes.py)."""
import trace_scopes


def read(summary, ctx):
    return trace_scopes.ms_per_round(ctx, "model.attention")

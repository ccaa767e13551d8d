"""post_norm_ms.round: device self time of the ``model.post_norm`` scope
(an encoder block's two post-LN residual norms, forward, remat and
backward) per traced round, in ms (bench/trace_scopes.py)."""
import trace_scopes


def read(summary, ctx):
    return trace_scopes.ms_per_round(ctx, "model.post_norm")

"""lowrank_apply_ms.round: device self time of the ``lowrank.apply`` scope
(the lift-free base GEMM and split matmul, the ``lowrank_linear`` kernel
included, forward and backward, less the norm probe) per traced round, in
ms (bench/trace_scopes.py)."""
import trace_scopes


def read(summary, ctx):
    return trace_scopes.ms_per_round(ctx, "lowrank.apply")

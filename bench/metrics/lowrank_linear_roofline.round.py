"""lowrank_linear_roofline.round: the fused lift-free apply's share of its
roofline in the traced rounds: over every ``lowrank_linear`` Pallas call in
the window, the least time (the larger of its operations and bytes, from
the shapes in the op's HLO text, over the chip's peaks; bench/flops
``lowrank_linear``) over the device time of the calls."""
import common
import flops
import trace_reduce

KIND = "lowrank_linear"


def least_time(name, peak):
    out, args = trace_reduce.shapes(name)
    _, x, w, basis, _ = args
    clients = 1
    for d in basis[1][:-2]:
        clients *= d
    tokens = 1
    for d in x[1][:-1]:
        tokens *= d
    m, n, r = x[1][-1], w[1][-1], basis[1][-1]
    return flops.min_time(*flops.lowrank_linear(tokens // clients, m, n, r,
                                                clients=clients), peak)


def read(summary, ctx):
    peak = common.peaks(ctx.device_kind)
    need = spent = 0.0
    for name, v in summary["ops"].items():
        if trace_reduce.op_kind(name) == KIND:
            need += v["count"] * least_time(name, peak)
            spent += v["seconds"]
    return 100.0 * need / spent if spent else None

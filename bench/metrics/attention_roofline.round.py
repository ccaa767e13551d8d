"""attention_roofline.round: the flash attention kernels' share of their
roofline in the traced rounds: over every call of the forward, dQ and dK/dV
kernels (``flash_attention_{fwd,dq,dkv}``, ``_causal`` when the call masks
the future) in the window, the least time (the larger of its operations
over the chip's bf16 peak and its bytes over its HBM bandwidth) over the
device time of the calls. None where the kernels never run.

Operations per call count the query-key pairs the mathematics needs (the
causal half, or every pair of a bidirectional call) and 2·d per pair for
each product the call's kind must form: QKᵀ and PV in the forward; QKᵀ,
dO·Vᵀ and dS·K for dQ; QKᵀ, Pᵀ·dO, dO·Vᵀ and dSᵀ·Q for dK/dV. Bytes count
each operand and output once, from the shapes in the op's HLO text."""
import re

import common
import flops
import trace_reduce

PRODUCTS = {"flash_attention_fwd": 2, "flash_attention_dq": 3,
            "flash_attention_dkv": 4}
_KIND = re.compile(r"^(flash_attention_(?:fwd|dq|dkv))(_causal)?$")


def _types(text):
    """[(dims, bytes)] of the array types in a piece of HLO text."""
    out = []
    for dt, dims in trace_reduce._TYPE.findall(text):
        d = [int(x) for x in dims.split(",") if x]
        n = trace_reduce._BYTES[dt]
        for x in d:
            n *= x
        out.append((d, n))
    return out


def least_time(name, peak):
    """Least time of one kernel call named by its HLO text, or None for an
    op that is not one of the kernels. Operands: q (..., Lq, H·d), k (...,
    Lk, Hkv·d) first; the output may be a tuple."""
    m = _KIND.match(trace_reduce.op_kind(name))
    if not m or " custom-call(" not in name:
        return None
    head, _, rest = name.split(" = ", 1)[1].partition(" custom-call(")
    args = _types(rest.split("), ", 1)[0])
    q, k = args[0][0], args[1][0]
    lq, lk, width = q[-2], k[-2], q[-1]
    rows = 1
    for x in q[:-2]:
        rows *= x
    pairs = lq * (lk - lq) + lq * (lq + 1) // 2 if m.group(2) else lq * lk
    ops = PRODUCTS[m.group(1)] * 2 * width * pairs * rows
    byts = sum(b for _, b in args) + sum(b for _, b in _types(head))
    return flops.min_time(ops, byts, peak)


def read(summary, ctx):
    peak = common.peaks(ctx.device_kind)
    need = spent = 0.0
    for name, v in summary["ops"].items():
        t = least_time(name, peak)
        if t is not None:
            need += v["count"] * t
            spent += v["seconds"]
    return 100.0 * need / spent if spent else None

"""attention_roofline.round's count on op names written as the trace writes
a device op: its HLO text, with a tuple output and typed operands."""
import pytest

import common

PEAK = common.peaks("TPU v5 lite")
LAYOUT = "{3,2,1,0:T(8,128)(2,1)}"


class Ctx:
    device_kind = "TPU v5 lite"


def _arr(dt, dims):
    return f"{dt}[{','.join(map(str, dims))}]{LAYOUT}"


def _name(kind, q, k, outs, extra=()):
    """``%<kind>.3 = (outs) custom-call(q, k, k, *extra), ...``"""
    out = ", ".join(_arr(dt, d) for dt, d in outs)
    if len(outs) > 1:
        out = f"({out})"
    ops = [_arr("bf16", q), _arr("bf16", k), _arr("bf16", k)] + [
        _arr(dt, d) for dt, d in extra]
    args = ", ".join(f"{a} %p{i}" for i, a in enumerate(ops))
    return (f"%{kind}.3 = {out} custom-call({args}), custom_call_target="
            f'"tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}')


def _n(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _reader():
    return common.metric_reader("attention_roofline.round")


# 2 clients x 2 sequences, L = 256, 4 heads of 64 (query lanes 256)
Q, K = [2, 2, 256, 256], [2, 2, 256, 128]
LSE = [2, 2, 2, 2, 256]
Q_BYTES, K_BYTES, LSE_BYTES = 2 * 2 * 256 * 256 * 2, 2 * 2 * 256 * 128 * 2, \
    2 * 2 * 2 * 2 * 256 * 4


@pytest.mark.parametrize("kind,products,causal,outs,extra", [
    ("flash_attention_fwd_causal", 2, True, [("bf16", Q), ("f32", LSE)], []),
    ("flash_attention_fwd", 2, False, [("bf16", Q), ("f32", LSE)], []),
    ("flash_attention_dq_causal", 3, True, [("bf16", Q), ("f32", LSE)],
     [("bf16", Q), ("bf16", Q), ("f32", LSE)]),
    ("flash_attention_dkv_causal", 4, True, [("bf16", K), ("bf16", K)],
     [("bf16", Q), ("f32", LSE), ("f32", LSE)]),
])
def test_least_time_counts(kind, products, causal, outs, extra):
    pairs = 256 * 257 // 2 if causal else 256 * 256
    ops = products * 2 * 256 * pairs * 4
    byts = Q_BYTES + 2 * K_BYTES + sum(
        {"bf16": 2, "f32": 4}[dt] * _n(d) for dt, d in outs + extra)
    want = max(ops / PEAK["bf16_flops"], byts / PEAK["hbm_bytes_per_s"])
    got = _reader().least_time(_name(kind, Q, K, outs, extra), PEAK)
    assert got == pytest.approx(want, rel=1e-12)


def test_share_over_calls_and_none_without_kernel():
    fwd = _name("flash_attention_fwd_causal", Q, K,
                [("bf16", Q), ("f32", LSE)])
    other = "%fusion.7 = bf16[4,256]{1,0} fusion(bf16[4,256]{1,0} %p0)"
    reader = _reader()
    t = reader.least_time(fwd, PEAK)
    summary = {"ops": {fwd: {"count": 3, "seconds": 12 * t,
                             "self_seconds": 12 * t},
                       other: {"count": 1, "seconds": 1.0,
                               "self_seconds": 1.0}}}
    assert reader.read(summary, Ctx()) == pytest.approx(25.0)
    assert reader.least_time(other, PEAK) is None
    assert reader.read({"ops": {other: summary["ops"][other]}}, Ctx()) is None

"""mfu_encoder.round: an encoder cell's traced rounds' required operations
(bench/flops/encoder.py ``round_required``: every query-key pair, the head
at the ``<s>`` row) over the traced window times the chips' bf16 peak."""
import common
from flops import encoder


def read(summary, ctx):
    rounds = (summary.get("work") or {}).get("rounds")
    if not rounds or not summary["window_s"]:
        return None
    peak = common.peaks(ctx.device_kind)["bf16_flops"]
    need = encoder.round_required(ctx.conf["arch"], ctx.cell["fed"]) * rounds
    return 100.0 * need / (summary["window_s"] * ctx.chips * peak)

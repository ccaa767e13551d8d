"""mfu.round: the traced rounds' required operations (bench/flops
``round_required``) over the traced window times the chips' bf16 peak."""
import common
import flops


def read(summary, ctx):
    rounds = (summary.get("work") or {}).get("rounds")
    if not rounds or not summary["window_s"]:
        return None
    peak = common.peaks(ctx.device_kind)["bf16_flops"]
    need = flops.round_required(ctx.conf["arch"], ctx.cell["fed"]) * rounds
    return 100.0 * need / (summary["window_s"] * ctx.chips * peak)

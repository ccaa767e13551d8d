"""idle_share.round: the share of the traced window in which no operation
ran on the device (1 - busy / window), averaged over the chips."""


def read(summary, ctx):
    if not summary["window_s"]:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])

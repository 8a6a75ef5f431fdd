"""idle_share: the share of the traced window in which no operation runs on
a chip, in percent, averaged over the chips the cell uses."""


def read(ctx):
    if ctx.summary is None or not ctx.summary.chips:
        return None
    w = ctx.summary.window_ns
    chips = ctx.summary.chips
    return 100.0 * sum(1.0 - c.busy / w for c in chips) / len(chips)

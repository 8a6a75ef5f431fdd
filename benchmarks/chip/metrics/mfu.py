"""mfu: the whole step's share of the chips' bf16 peak, in percent.

Model FLOPs per step (``flops.py``, recompute not counted) times the steps
completed in the traced window, over the window's length times the chips
times the published peak of the running ``device_kind`` (``peaks.py``).
"""


def read(ctx):
    if ctx.summary is None or ctx.steps <= 0:
        return None
    window_s = ctx.summary.window_ns * 1e-9
    return (100.0 * ctx.flops_per_step * ctx.steps
            / (window_s * ctx.chips * ctx.peak_flops))

"""coll_ms: device time of collective operations per step, in ms, averaged
over the chips. Nothing to read where no collective ran."""


def read(ctx):
    s = ctx.summary
    if s is None or ctx.steps <= 0 or not any(c.coll > 0 for c in s.chips):
        return None
    return sum(c.coll for c in s.chips) / len(s.chips) / ctx.steps * 1e-6

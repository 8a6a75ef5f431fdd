"""optimizer_ms: device self time per step of the operations under the
program's ``adamw`` named scope (``optim/adamw.update``), in ms, averaged
over the chips (``scopes.py``). Nothing to read where no operation
carries the scope."""


def read(ctx):
    from benchmarks.chip import scopes
    return scopes.scope_ms_per_step(ctx, "adamw")

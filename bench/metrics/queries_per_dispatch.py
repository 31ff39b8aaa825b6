"""queries_per_dispatch: queries answered per serving dispatch over the
window (``MultiTenantService.dispatches``, a program counter); the rest
of each dispatch's slots are padding."""


def read(ctx):
    """The metric from the run's context, or None."""
    n = ctx.get("dispatches")
    if not n:
        return None
    return ctx["served"] / n

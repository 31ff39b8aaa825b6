"""cd_ms: the CD layer, ``core/peelspec.cd_loop`` (coarse range rounds).

Mean over the window's jobs (after the profiled one) of span ``cd``,
in milliseconds; nothing without that span."""


def read(ctx):
    """The metric from the run's context, or None."""
    spans = ctx.get("spans")
    if spans is None:
        return None
    per_job = spans.per_job("cd", ctx.get("jobs"))
    return 1e3 * sum(per_job) / len(per_job) if per_job else None

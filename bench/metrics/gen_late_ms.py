"""gen_late_ms: the load generator's 99th percentile of how late it
released a query after the query was due, in milliseconds."""


def read(ctx):
    """The metric from the run's context, or None."""
    return ctx.get("gen_late_p99_ms")

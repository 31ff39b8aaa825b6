"""build_ms: the hierarchy build, ``hierarchy/build.build_hierarchy``.

Mean over the window's jobs (after the profiled one) of span ``build``,
in milliseconds; nothing without that span."""


def read(ctx):
    """The metric from the run's context, or None."""
    spans = ctx.get("spans")
    if spans is None:
        return None
    per_job = spans.per_job("build", ctx.get("jobs"))
    return 1e3 * sum(per_job) / len(per_job) if per_job else None

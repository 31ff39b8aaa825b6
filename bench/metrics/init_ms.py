"""init_ms: the ⋈init layer, ``core/peel.build_peel_spec`` (wedge lists and
initial butterfly supports, ``core/csr``), until its device arrays are ready.

Mean over the window's jobs (after the profiled one) of span ``init``,
in milliseconds; nothing without that span."""


def read(ctx):
    """The metric from the run's context, or None."""
    spans = ctx.get("spans")
    if spans is None:
        return None
    per_job = spans.per_job("init", ctx.get("jobs"))
    return 1e3 * sum(per_job) / len(per_job) if per_job else None

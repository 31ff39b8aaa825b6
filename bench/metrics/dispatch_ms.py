"""dispatch_ms: mean time of one serving dispatch
(``hierarchy/multiserve._answer_batch_multi``, until its answers are
ready), over the traced window's dispatches, in milliseconds."""


def read(ctx):
    """The metric from the run's context, or None."""
    spans = ctx.get("spans")
    if spans is None:
        return None
    d = spans.window("dispatch")
    return 1e3 * sum(d) / len(d) if d else None

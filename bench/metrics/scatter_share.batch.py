"""scatter_share.batch: time of device operations that scatter (their
``tf_op`` ends in a scatter, such as ``scatter-add`` or ``scatter-min``),
as a share of device busy time in the traced job (%)."""


def read(ctx):
    """The metric from the run's context, or None."""
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["scatter_s"] / tr["busy_s"]

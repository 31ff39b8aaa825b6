"""fd_rounds_max: the FD critical path of a job, ``PeelStats.rho_fd_max``
(rounds of the longest partition peel; an exact count)."""


def read(ctx):
    """The metric from the run's context, or None."""
    stats = ctx.get("stats")
    if not stats or "rho_fd_max" not in stats[0]:
        return None
    return sum(s["rho_fd_max"] for s in stats) / len(stats)

"""cd_rounds: CD rounds of a job, ``PeelStats.rho_cd`` (an exact count)."""


def read(ctx):
    """The metric from the run's context, or None."""
    stats = ctx.get("stats")
    if not stats or "rho_cd" not in stats[0]:
        return None
    return sum(s["rho_cd"] for s in stats) / len(stats)

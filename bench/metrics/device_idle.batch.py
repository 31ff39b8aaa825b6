"""device_idle: share of the traced window in which no operation ran on
the device, 1 - busy / window, busy being the union of the device
operations' intervals (%)."""


def read(ctx):
    """The metric from the run's context, or None."""
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

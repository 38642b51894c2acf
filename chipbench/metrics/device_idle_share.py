"""device_idle_share: percentage of the traced loops' time in which no
operation ran on the device (1 - union of the ``XLA Ops`` intervals)."""


def read(ctx):
    tr = ctx.trace
    if not tr or not tr["n_devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

"""Share of the traced window in which no operation ran on the device (the
mean over chips of the busy union), left to the host's chunk loop."""


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["window_s"])

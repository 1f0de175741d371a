"""Device time per step of the XLA program outside Pallas kernels and
collectives (model forward and backward, jnp aggregation, optimizer), the
mean over chips."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    if not devs:
        return None
    other = sum(d["other"] for d in devs.values()) / len(devs)
    return 1e3 * other / ctx["steps"]

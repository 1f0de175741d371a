"""Device time per step of the Pallas kernels (TPU custom calls), the mean
over chips: the complement of ``xla_ms_per_step`` among the innermost
operations that are not collectives."""


def read(ctx):
    devs = ctx["trace"]["devices"]
    if not devs:
        return None
    kernels = sum(d["custom_call"] for d in devs.values()) / len(devs)
    return 1e3 * kernels / ctx["steps"]

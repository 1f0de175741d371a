"""Model FLOP/s utilisation of the whole BTARD step: the configuration's
model FLOPs per token (bench/flops.py) times the window's tokens per second,
over the chips' bf16 peak."""


def read(ctx):
    if ctx["peaks"] is None:  # no chip, no peak
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak

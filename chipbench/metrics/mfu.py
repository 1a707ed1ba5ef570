"""mfu: the forward FLOPs Algorithm 1 requires per round (flops.py), over
the window's rounds and seconds, as a share of the chips' bf16 peak."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_round"] * ctx["rounds"] / ctx["window_s"] \
        / peak

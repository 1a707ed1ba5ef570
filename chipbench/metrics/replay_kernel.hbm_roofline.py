"""replay_kernel.hbm_roofline: the least time the Pallas zo_replay_flat
calls of the window could take at the chip's HBM bandwidth (the bytes they
must move, flops.replay_kernel_bytes), as a share of their device time in
the trace. A memory bound only: v5e publishes no VPU peak, and the kernel
is bound by its hash and Box-Muller work, so the share reads low."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["replay_bytes_per_round"] == 0:
        return None
    t = tr["kernel_s"].get("zo_replay", 0.0)
    if t <= 0:
        return None
    need = ctx["replay_bytes_per_round"] * ctx["rounds"] \
        / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / t

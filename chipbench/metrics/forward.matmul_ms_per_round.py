"""forward.matmul_ms_per_round: device time of matmul ops (dots,
convolutions and the fusions that hold one) per round, from the trace."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["n_ops"] == 0:
        return None
    return 1e3 * tr["matmul_s"] / ctx["rounds"]

"""zo.other_ms_per_round: device time of every op that is not a matmul
(noise, perturbation, replay, norms, softmax, copies) per round."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["n_ops"] == 0:
        return None
    return 1e3 * tr["other_s"] / ctx["rounds"]

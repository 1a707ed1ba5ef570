"""Forward FLOPs that one MU-SplitFed round requires (Algorithm 1).

Per client and round: three client forwards (h, h+, h-) and 2τP + 2
server forwards (τ SPSA steps of P two-sided perturbations, and the
ZO-backprop pair). The round-start evaluation forward that produces the
reported loss is not required by the algorithm and is not counted.

Convention, per token of a forward:
  * 2 FLOPs per matmul weight: attention q, k, v, o projections, the MLP
    (three d_model x d_ff matrices for SwiGLU, two for GELU) and, on the
    server, the head (d_model x vocab);
  * attention scores and the weighted sum, QK^T + AV, as 4 * S * d_model
    per layer (H * d_head = d_model), with no halving for the causal mask;
  * the embedding lookup is a gather and counts nothing.
"""
from __future__ import annotations


def layer_weights(model: dict) -> int:
    d, H, Hkv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = model.get("d_head") or d // H
    attn = d * H * dh * 2 + d * Hkv * dh * 2
    mlp = (3 if model.get("mlp_type", "swiglu") == "swiglu" else 2) \
        * d * model["d_ff"]
    return attn + mlp


def per_token(model: dict, n_layers: int, seq: int, head: bool) -> int:
    """FLOPs per token of a forward through ``n_layers`` layers."""
    d, H = model["d_model"], model["n_heads"]
    dh = model.get("d_head") or d // H
    f = n_layers * (2 * layer_weights(model) + 4 * seq * H * dh)
    if head:
        f += 2 * d * model["vocab_size"]
    return f


def round_flops(model: dict, cut_units: int, traffic: dict) -> int:
    """Required forward FLOPs of one round of ``traffic`` on ``model``."""
    S = traffic["seq"]
    tokens = traffic["batch"] * S                  # per client forward
    client = 3 * per_token(model, cut_units, S, head=False)
    n_server = 2 * traffic["tau"] * traffic["perturbations"] + 2
    server = n_server * per_token(model, model["n_layers"] - cut_units, S,
                                  head=True)
    return traffic["clients"] * tokens * (client + server)


def replay_kernel_bytes(layout, cut_units: int, traffic: dict) -> int:
    """HBM bytes per round that the counter-noise replay kernel must move:
    each call reads and writes one whole leaf. Per round it is called on
    every server leaf once per SPSA step of every client (the tau-loop
    update of the working copy) and once more for the aggregation, and on
    every client leaf once for the aggregation. ``layout`` is the
    parameter tree as shapes, with stacked ``units``. 0 for other noise."""
    if traffic["noise"] != "counter":
        return 0
    import jax
    import numpy as np

    def nbytes(tree, lo, hi):
        return sum(int(np.prod(x.shape[1:])) * (min(hi, x.shape[0]) - lo)
                   * x.dtype.itemsize for x in jax.tree.leaves(tree))

    units = layout["units"]
    n_units = jax.tree.leaves(units)[0].shape[0]
    whole = lambda t: sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in jax.tree.leaves(t))
    client = whole(layout["embed"]) + nbytes(units, 0, cut_units)
    server = (whole(layout["lm_head"]) + whole(layout["final_norm"])
              + nbytes(units, cut_units, n_units))
    updates = traffic["clients"] * traffic["tau"] + 1
    return 2 * (updates * server + client)

"""flops.round_flops against hand counts for the three cells."""
import json

import pytest

from chipbench import flops, traffic
from chipbench.tests.conftest import ROOT


def config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


# Per token and layer: 2 FLOPs per weight (q, k, v, o, three MLP matrices)
# plus 4*S*d_model for QK^T and AV. olmo-1b: 4*2048*2048 + 3*2048*8192 =
# 67,108,864 weights. internlm2-1.8b (8 kv heads of 128): 2*2048*2048 +
# 2*2048*1024 + 3*2048*8192 = 62,914,560 weights.
OLMO_W, INTERN_W = 67_108_864, 62_914_560


def hand(weights, n_layers, vocab, d, M, b, S, tau, cut=2):
    layer = 2 * weights + 4 * S * d
    client = 3 * cut * layer                       # h, h+, h-
    server = (2 * tau + 2) * ((n_layers - cut) * layer + 2 * d * vocab)
    return M * b * S * (client + server)


@pytest.mark.parametrize("cfg, traffic_name, want, tflop", [
    ("olmo-1b", "silo", hand(OLMO_W, 16, 50304, 2048, 4, 4, 1024, 2),
     230.54),
    ("internlm2-1.8b", "silo-counter",
     hand(INTERN_W, 24, 92544, 2048, 4, 4, 1024, 2), 340.73),
    ("olmo-1b", "edge", hand(OLMO_W, 16, 50304, 2048, 16, 1, 128, 2),
     27.46),
])
def test_round_flops_match_hand_count(cfg, traffic_name, want, tflop):
    doc = config(cfg)
    got = flops.round_flops(doc["model"], doc["cut_units"],
                            traffic.load(traffic_name))
    assert got == want
    assert round(got / 1e12, 2) == tflop


def test_layer_weights_count_gqa_projections():
    doc = config("internlm2-1.8b")
    assert flops.layer_weights(doc["model"]) == INTERN_W
    assert flops.layer_weights(config("olmo-1b")["model"]) == OLMO_W

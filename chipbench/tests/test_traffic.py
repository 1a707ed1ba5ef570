"""The traffic generator and the optional schedule of a traffic file."""
import json

import numpy as np
import pytest

from chipbench import cell, traffic

SEED = 2 ** 40 + 11


@pytest.mark.parametrize("name", ["silo", "silo-counter", "edge"])
def test_batches_follow_the_seed_and_keep_their_sizes(name):
    t = traffic.load(name)
    t.update(samples_per_client=8, seq=16)
    a = traffic.make_batch_fn(t, 512, SEED)
    b = traffic.make_batch_fn(t, 512, SEED)
    other = traffic.make_batch_fn(t, 512, SEED + 1)
    shape = (t["clients"], t["batch"], t["seq"])
    for r in (0, 1, 7):
        x, y, z = a(r), b(r), other(r)
        assert x["tokens"].shape == x["labels"].shape == shape
        assert x["tokens"].dtype == np.int32
        assert z["tokens"].shape == shape
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["tokens"][..., 1:],
                                      x["labels"][..., :-1])
        assert 0 <= x["tokens"].min() and x["labels"].max() < 512
        for m in range(t["clients"]):     # b distinct rows of the pool
            assert len({row.tobytes() for row in x["tokens"][m]}) == t["batch"]
    assert not np.array_equal(a(0)["tokens"], a(1)["tokens"])
    assert not np.array_equal(a(0)["tokens"], other(0)["tokens"])


def test_a_traffic_file_needs_enough_samples_for_a_batch(tmp_path,
                                                         monkeypatch):
    doc = dict(traffic.load("silo"), samples_per_client=2)
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "few.json").write_text(json.dumps(doc))
    monkeypatch.setattr(traffic, "HERE", tmp_path)
    with pytest.raises(ValueError, match="samples_per_client"):
        traffic.load("few")


def test_the_schedule_key_reaches_the_program_schedule():
    from repro.core import straggler
    doc = {"arch": "olmo-1b", "cut_units": 1, "model": {
        "n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
        "d_head": 32, "d_ff": 128, "vocab_size": 256}}
    t = dict(traffic.load("edge"), participation=0.5,
             schedule={"straggler_scale": 2.0, "deadline": 1.5})
    job = cell.Job(doc, t, SEED)
    want = straggler.make_schedule(SEED, cell.SCHEDULE_ROUNDS, t["clients"],
                                   participation=0.5, straggler_scale=2.0,
                                   deadline=1.5)
    np.testing.assert_array_equal(job.sched.masks, want.masks)
    assert 0 < job.masks(4).sum() < 4 * t["clients"]
    plain = cell.Job(doc, traffic.load("edge"), SEED)
    assert plain.masks(4).min() == 1.0

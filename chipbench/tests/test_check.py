"""The check that decides ``correct``, at a size a CPU holds: a sound run
passes; the control (the reference with float8 matmul operands and
bfloat16 state, in the program's place) and each fault planted under the
timed path fail.

The run skips only the look for a chip: ``cell.run_docs`` drives set-up,
the window and the check as ``run.py`` does. The limits here are this
size's own, set from its readings on the CPU at SEED (bfloat16 program vs
float32 reference at d_model 128): sound runs read loss_gap 3.5e-5
(gaussian) and 2.2e-5 (counter), client_loss_gap 8.3e-5, update_gap
0.045 and 0.043; the float8 control (gaussian) reads loss_gap 5.4e-5,
client_loss_gap 5.2e-4 and update_gap 0.217; half_batch (gaussian)
loss_gap 5.4e-3 and client_loss_gap 3.0e-3; unchanged update_gap 1.
"""
import time

import pytest

from chipbench import cell, check, faults, traffic

MODEL = {"n_layers": 3, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "d_head": 32, "d_ff": 256, "vocab_size": 512, "norm_type": "rmsnorm",
         "mlp_type": "swiglu", "attn_impl": "gqa", "qk_norm": False,
         "sliding_window": 0, "rope_theta": 10000.0,
         "tie_embeddings": False, "dtype": "bfloat16"}
DOC = {"arch": "internlm2-1.8b", "cut_units": 1, "model": MODEL}
LIMITS = {"loss_gap": 2e-4, "client_loss_gap": 2.5e-4, "update_gap": 0.15}
SEED = 2 ** 31 + 7
BENCH = {"end_to_end": [
    {"name": "tokens_per_s", "unit": "tokens/s"},
    {"name": "setup_s", "unit": "s"}], "per_layer": []}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def job_traffic(noise):
    t = traffic.load("silo")
    t.update(clients=2, batch=2, seq=32, noise=noise)
    return t


def run(noise):
    out, lines = cell.run_docs(
        BENCH, {"name": "tiny", "chips": 1}, DOC, job_traffic(noise), LIMITS,
        SEED, 1.0, False, DEVICE, PEAKS, t_start=time.perf_counter())
    return out


@pytest.mark.parametrize("noise", ["gaussian", "counter"])
def test_sound_run_is_correct(noise):
    out = run(noise)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["checks"]) == set(check.NUMBERS)
    assert list(out["checks"])[-len(LIMITS):] == list(LIMITS)
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def test_float8_control_is_not_correct():
    job = cell.Job(DOC, job_traffic("gaussian"), SEED)
    nums = check.numbers(job.reference("fp8"), job.reference("f32"))
    ok, checks = check.verdict(nums, LIMITS)
    assert not ok, checks


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(fault):
    with faults.planted(fault):
        out = run("gaussian")
    assert not out["correct"], out["checks"]


def test_reference_weighs_clients_by_the_schedule_masks():
    """In float32 the program and the reference agree to rounding with a
    schedule that leaves clients out."""
    doc = dict(DOC, model=dict(MODEL, dtype="float32"))
    t = dict(job_traffic("gaussian"), clients=4, participation=0.5)
    job = cell.Job(doc, t, SEED)
    masks = job.masks(job.C)
    assert 0 < masks.sum() < masks.size and masks.sum(1).min() > 0
    out, _ = cell.run_docs(
        BENCH, {"name": "tiny", "chips": 1}, doc, t,
        {"loss_gap": 1e-6, "client_loss_gap": 1e-6, "update_gap": 1e-3},
        SEED, 1.0,
        False, DEVICE, PEAKS, t_start=time.perf_counter())
    assert out["correct"], out["checks"]


def test_missing_limits_are_not_correct():
    ok, checks = check.verdict({"loss_gap": 0.0, "update_gap": 0.0}, None)
    assert not ok

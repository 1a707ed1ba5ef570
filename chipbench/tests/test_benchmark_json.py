"""BENCHMARK.json and every file it names keep to the benchmark's rules."""
import json
import re

import pytest

from chipbench import traffic
from chipbench.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_are_exactly_the_contract_s():
    assert set(BENCH) == KEYS["top"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert 1 <= len(BENCH[kind])
        for item in BENCH[kind]:
            extra = {"workloads"} if kind in ("end_to_end",
                                               "per_layer") else set()
            assert KEYS[kind] <= set(item) <= KEYS[kind] | extra, item


def test_names_units_and_lines():
    names = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for item in BENCH[kind]:
            assert NAME.match(item["name"]), item["name"]
            names.add((kind, item["name"]))
            if "unit" in item:
                assert UNIT.match(item["unit"]), item["unit"]
                assert item["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in item:
                    assert line(item[k]), (k, item[k])
    metric_names = [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for kind in ("configs", "workloads"):
        assert len({i["name"] for i in BENCH[kind]}) == len(BENCH[kind])
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    width = re.compile(r"(^d_|_dim$|_rank$|hidden|intermediate|head|"
                       r"expan|state|latent|proj|top_k|per_tok)")
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not width.search(k)
                   for k in c["reduced"]), c["reduced"]


def test_command_paths_and_run_seconds():
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for w in cmd:
        if "/" in w and not w.startswith("-"):
            assert any(w.startswith(p + "/") for p in BENCH["paths"]), w
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24                     # what later PRs may grow to
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_metrics_sources_bounds_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        for c in m.get("workloads", cells):
            assert reports(e2e[m["moves"]], c), (m["name"], c)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for c in cells:
        assert reports(e2e["setup_s"], c)
        assert any(reports(m, c) for n, m in e2e.items() if n != "setup_s")
        assert any(reports(m, c) for m in BENCH["per_layer"])


def test_every_config_keeps_a_cell_and_every_file_exists():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("chipbench/")
        assert c["file"] not in files
        files.add(c["file"])
        doc = json.loads(path.read_text())
        assert set(c["reduced"]) <= set(doc["model"]), c["reduced"]
        assert set(doc.get("reduced_why", {})) == set(c["reduced"])
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        traffic.load(w["traffic"])
        assert (ROOT / "chipbench" / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_config_files_state_what_the_program_runs(cfg):
    """Every size in the config file is a field of the program's config,
    and the run builds its config from the file."""
    import dataclasses
    from repro.configs import get_config
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    doc = json.loads((ROOT / entry["file"]).read_text())
    fields = {f.name for f in dataclasses.fields(get_config(doc["arch"]))}
    assert set(doc["model"]) <= fields
    built = get_config(doc["arch"]).replace(**doc["model"])
    assert all(getattr(built, k) == v for k, v in doc["model"].items())
    for k in ("deployment", "assumed", "source", "published"):
        assert doc.get(k)


# published key -> the program's config field that holds it
PUBLISHED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
             "rope_theta": "rope_theta"}


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_config_files_run_the_published_values(cfg):
    """Every published size the program has a field for is run as
    published, unless ``reduced`` lists it."""
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    doc = json.loads((ROOT / entry["file"]).read_text())
    for pub, field in PUBLISHED.items():
        if field not in entry["reduced"]:
            assert doc["model"][field] == doc["published"][pub], field


def test_peaks_refuse_an_unknown_device_kind():
    from chipbench import cell
    assert cell.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        cell.load_peaks("TPU v99")


def test_run_refuses_a_machine_without_a_tpu():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr

"""BENCHMARK.json and the files it names: the shape the benchmark's
contract asks for, every file found by name, and each configuration file
equal to the repo configuration it derives from, with its changes."""
import json
import os
import re

import bench_tiny
import pytest

from bench import compare, spec

ROOT = bench_tiny.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert ".." not in p and not p.startswith("/")
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in B[section]]
        assert len(names) == len(set(names))
        for e in B[section]:
            assert set(e) - {"workloads"} == want, (section, e["name"])
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(w):
    cell = spec.load(w["name"])
    assert w["chips"] == 1
    compared = {k for k in cell.limits if k in compare.NUMBERS}
    assert {"grad_gap", "grad1_gap", "change_gap"} <= compared
    assert set(cell.limits["not_compared"]) == set(compare.NUMBERS) - compared
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]).read)
    assert os.path.exists(os.path.join(
        ROOT, "bench", "runners", cell.traffic["runner"] + ".py"))


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_repo_config_with_its_changes(c):
    from repro.config import ModelConfig, replace
    from repro.models import model_zoo
    with open(os.path.join(ROOT, c["file"])) as f:
        conf = json.load(f)
    assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    repo = model_zoo.get_config("internvl2-2b")
    changed = {k: v[1] for k, v in conf["changed"].items()}
    for k, (old, _) in conf["changed"].items():
        assert getattr(repo, k) == old
    want = replace(repo, name=conf["model"]["name"], **changed)
    got = ModelConfig(**conf["model"])
    assert got == want
    pub = conf["published"]
    m = conf["model"]
    assert (pub["hidden_size"], pub["num_hidden_layers"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["intermediate_size"], pub["vocab_size"]) == (
        m["d_model"], m["num_layers"], m["num_heads"], m["num_kv_heads"],
        m["d_ff"], m["vocab_size"])


def test_peaks_refuse_an_unknown_chip():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_run_refuses_a_host_without_a_tpu():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", B["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_a_directory_with_only_the_benchmark(tmp_path):
    import shutil
    import subprocess
    import sys
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in B["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         B["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "no program" in p.stderr

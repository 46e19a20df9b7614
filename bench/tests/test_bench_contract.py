"""BENCHMARK.json and the files it names, and the command's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_keys_names_and_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        cfg = harness.load_json(ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
        assert os.path.exists(os.path.join(harness.BENCH, "services",
                                           cfg["service"] + ".py"))
        assert os.path.exists(os.path.join(harness.BENCH, "graphs",
                                           cfg["graph"]["generator"] + ".py"))
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        traffic = harness.load_json(harness.BENCH, "traffic",
                                    w["traffic"] + ".json")
        for kind in ("client", "generator"):
            assert os.path.exists(os.path.join(
                harness.BENCH, kind + "s", traffic[kind] + ".py"))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"}
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
        layers.setdefault(m["layer"], m["name"])
    for w in cells:  # every cell: setup_s, another end-to-end, a per-layer
        assert sum(harness.applies(m, {"name": w}) for m in
                   bench["end_to_end"]) >= 2
        assert any(w in m["workloads"] for m in bench["per_layer"])


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "lj-decompose.semicore-star", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_peaks_table_refuses_unknown_devices():
    from bench import readings

    assert readings.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        readings.peak("cpu", "hbm_bytes_per_s")
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        assert json.load(f)["source"].startswith("Google Cloud")

"""The four-chip cell on a forced four-device CPU host at a small size, its
per-layer readers, and the collective reduction."""
import json
import os
import subprocess
import sys

import pytest

from bench import collectives, devtrace, harness
from bench.devtrace import Event

CELL = "lj-shard-decompose.semicore-star"
NEW = ("fixpoint_ms_per_pass.shard", "fixpoint_roofline.shard",
       "allgather_ms_per_pass.shard", "device_idle_pct.shard",
       "outside_chunks_ms.shard", "globalize_ms.shard",
       "allgather_mib.shard", "shard_pad_pct.shard",
       "peak_hbm_mib_max.shard")

RUN = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
from bench import harness, readings
harness.CACHE_DIR = sys.argv[2]
peak = readings.peak
readings.peak = lambda kind, what: peak("TPU v5 lite", what)
tiny = {"nodes": 3000, "edges": 40000}
out = {}
for trace in (False, True):
    out[str(trace)], _ = harness.run_cell(
        "lj-shard-decompose.semicore-star", 2**31 + 11, 0.5, trace,
        started=0.0, require_tpu=False, overrides=tiny)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """An untraced and a traced run of the cell in one process."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-c", RUN, harness.ROOT,
         str(tmp_path_factory.mktemp("jax-cache"))], cwd=harness.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_names_its_config_traffic_and_metrics():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, config, traffic = harness.cell_spec(bench, CELL)
    assert cell["chips"] == config["chips"] == 4
    assert config["backend"] == "shard" and config["service"] == "decompose"
    assert traffic["client"] == "closed_loop"
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "decompose_s"
    e2e = {m["name"] for m in bench["end_to_end"] if harness.applies(m, cell)}
    assert e2e == {"decompose_s", "peak_hbm_mib", "setup_s"}


def test_an_untraced_run_is_correct_on_four_devices(runs):
    result = runs["False"]
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert {"decompose_s", "setup_s"} <= set(result["metrics"])
    assert result["checks"]["core_mismatch_nodes"]["value"] == 0
    assert result["checks"]["cnt_mismatch_nodes"]["value"] == 0


def test_a_traced_run_reads_every_new_metric(runs):
    result = runs["True"]
    assert result["correct"]
    metrics = result["metrics"]
    # the CPU keeps no allocator statistics: the fullest chip's peak is a
    # device reading, and absent here
    assert set(metrics) == set(NEW) - {"peak_hbm_mib_max.shard"}
    assert all(v["value"] >= 0 for v in metrics.values())
    for name in ("fixpoint_ms_per_pass.shard", "allgather_ms_per_pass.shard",
                 "allgather_mib.shard", "outside_chunks_ms.shard",
                 "globalize_ms.shard"):
        assert metrics[name]["value"] > 0, name
    assert metrics["fixpoint_roofline.shard"]["value"] <= 100


def test_the_fullest_chip_is_read(monkeypatch):
    import jax

    class Chip:
        def __init__(self, peak):
            self.peak = peak

        def memory_stats(self):
            return None if self.peak is None else {
                "peak_bytes_in_use": self.peak}

    metric = harness.load_module(os.path.join(
        harness.BENCH, "metrics", "peak_hbm_mib_max.shard.py"))

    class Run:
        cell = {"chips": 4}

    monkeypatch.setattr(jax, "devices", lambda: [
        Chip(10 * 2**20), Chip(30 * 2**20), Chip(20 * 2**20), Chip(None),
        Chip(99 * 2**20)])
    assert metric.read(Run()) == 30  # the fifth device is not the cell's
    monkeypatch.setattr(jax, "devices", lambda: [Chip(None)] * 4)
    assert metric.read(Run()) is None


def test_collectives_are_summed_over_two_planes():
    """Two chips run ``jit_chunk_x_shard`` over [0, 100] ns: chip 0 an
    all-reduce over [10, 30] and a psum over [50, 55], chip 1 an
    async all-gather over [20, 25] and [25, 40]; a fusion that reads the
    all-reduce's result is no collective, nor is another program's."""
    planes = ("/device:TPU:0", "/device:TPU:1")
    ops = devtrace.device_ops([
        *(Event(p, "XLA Modules", "jit_chunk_x_shard(1)", 0, 100)
          for p in planes),
        Event(planes[0], "XLA Ops", "all-reduce.15", 10, 20),
        Event(planes[0], "XLA Ops", "%psum.6 = s32[] all-reduce(s32[] %a)",
              50, 5),
        Event(planes[0], "XLA Ops",
              "%fusion.12 = s32[8] fusion(s32[8] %all-reduce.15)", 30, 10),
        Event(planes[1], "XLA Ops", "all-gather-start.2", 20, 5),
        Event(planes[1], "XLA Ops", "all-gather-done.2", 25, 15),
        Event(planes[1], "XLA Modules", "jit_other(2)", 200, 10),
        Event(planes[1], "XLA Ops", "all-reduce.1", 200, 10),
    ])
    assert [collectives.is_collective(e) for e in ops] == [
        True, True, False, True, True, True]
    assert collectives.collective_ns(ops, "jit_chunk") == (25 + 20) / 2
    assert collectives.collective_ns(ops, "jit_other") == 10 / 1
    assert collectives.collective_ns(ops, "jit_none") == 0.0
    cpu = [Event("/host:CPU", "", "all_gather.4", 0, 8,
                 {"hlo_op": "all_gather.4", "hlo_module": "jit_chunk_s"})]
    assert collectives.collective_ns(devtrace.device_ops(cpu),
                                     "jit_chunk") == 8

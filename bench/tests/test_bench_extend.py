"""A new graph family, request generator, client, mix, configuration and
metric are new files and entries: a copy of the benchmark with only files
added runs a new cell, correct, on the CPU at a small size."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from bench import harness

RING = '''
"""Circulant graph: node i linked to i+1, ..., i+degree (mod nodes)."""
import numpy as np

from bench.graph import Graph


def structure(spec, nodes, edges):
    d = edges // nodes
    i = np.repeat(np.arange(nodes, dtype=np.int64), d)
    j = (i + np.tile(np.arange(1, d + 1), nodes)) % nodes
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    keys = np.unique(lo * nodes + hi)
    return Graph.from_keys(nodes, keys)
'''

ZIPF = '''
"""Inserts only, both endpoints drawn from a Zipf law over the nodes."""
import numpy as np


class ZipfInserts:
    def __init__(self, run):
        g = run.graph
        self.n = g.n
        self.keys = set(g.keys().tolist())
        self.rng = np.random.default_rng([run.seed, 3])
        self.a = float(run.traffic["zipf_a"])
        self.size = int(run.traffic["batch_ops"])
        self.batches = []

    def _node(self):
        return int(self.rng.zipf(self.a) - 1) % self.n

    def request(self, i):
        while len(self.batches) <= i:
            ops = []
            while len(ops) < self.size:
                u, v = self._node(), self._node()
                lo, hi = min(u, v), max(u, v)
                if u != v and lo * self.n + hi not in self.keys:
                    self.keys.add(lo * self.n + hi)
                    ops.append(("+", lo, hi))
            self.batches.append(ops)
        return self.batches[i]


def make(run):
    return ZipfInserts(run)
'''

PACED = '''
"""Open loop: request i is due at i * interval_s after the window opens and
is sent then, or as soon as the one before has been answered."""
import time


def warm_up(run, service):
    for i in range(int(run.traffic.get("warmup", 0))):
        service.request(run, run.requests.request(i))


def window(run, service):
    first = int(run.traffic.get("warmup", 0))
    step = float(run.traffic["interval_s"])
    t0 = time.perf_counter()
    i = first
    while True:
        due = t0 + (i - first) * step
        time.sleep(max(0.0, due - time.perf_counter()))
        run.attempted += 1
        req = run.requests.request(i)
        try:
            reply = service.request(run, req)
        except Exception as e:
            run.fail(e)
            break
        te = time.perf_counter()
        service.observe(run, reply)
        run.units.append({"name": service.UNIT, "start": due, "end": te,
                          "passes": reply["passes"], "ops": reply["ops"]})
        i += 1
        if te - t0 >= run.seconds:
            break
    run.window_s = time.perf_counter() - t0
'''

LATENCY = '''
"""Slowest answer of the window, from its due time."""


def read(run):
    return max((u["end"] - u["start"]) * 1e3 for u in run.units) \\
        if run.units else None
'''

RUN = '''
import json, sys
sys.path.insert(0, sys.argv[1])
from bench import harness
result, _ = harness.run_cell("ring-maintain.zipf-paced", 2**31 + 9, 0.5,
                             False, started=0.0, require_tpu=False)
print(json.dumps(result))
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text).lstrip())


def test_a_cell_of_new_kinds_is_new_files_and_entries(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    os.symlink(os.path.join(harness.ROOT, "src"), root / "src")
    before = {p: open(p, "rb").read() for p in (
        str(f) for f in (root / "bench").rglob("*") if f.is_file())}

    b = root / "bench"
    _write(str(b / "graphs" / "ring.py"), RING)
    _write(str(b / "generators" / "zipf_inserts.py"), ZIPF)
    _write(str(b / "clients" / "paced.py"), PACED)
    _write(str(b / "metrics" / "slowest_ms.zipf.py"), LATENCY)
    _write(str(b / "metrics" / "updates_per_s.zipf.py"),
           open(os.path.join(harness.BENCH, "metrics",
                             "updates_per_s.mixed.py")).read())
    _write(str(b / "traffic" / "zipf-paced.json"), json.dumps({
        "client": "paced", "generator": "zipf_inserts", "batch_ops": 16,
        "zipf_a": 1.6, "interval_s": 0.05, "warmup": 1,
        "queries": {"coreness_of_touched": True, "top_k": 5,
                    "degeneracy": True}}))
    _write(str(b / "configs" / "ring-maintain.json"), json.dumps({
        "name": "ring-maintain", "nodes": 2000, "edges": 6000,
        "reduced": [], "graph": {"generator": "ring", "structure_seed": 1},
        "service": "writer", "backend": "xla", "wal_fsync": True,
        "chips": 1}))

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": "ring-maintain", "source": "https://example.org/ring",
        "file": "bench/configs/ring-maintain.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "ring-maintain.zipf-paced", "config": "ring-maintain",
        "traffic": "zipf-paced", "chips": 1, "why": "a test"})
    for name, unit, better in (("updates_per_s.zipf", "updates/s", "higher"),
                               ("slowest_ms.zipf", "ms", "lower")):
        bench["end_to_end"].append({
            "name": name, "unit": unit, "better": better, "bound": 0.05,
            "source": "host_clock", "workloads": ["ring-maintain.zipf-paced"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", RUN, str(root)], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"], p.stderr[-3000:]
    assert result["checks"]["batches_checked"]["value"] >= 2
    assert {"updates_per_s.zipf", "slowest_ms.zipf", "setup_s"} <= set(
        result["metrics"])
    # no file the benchmark had was changed
    assert all(open(p, "rb").read() == data for p, data in before.items())

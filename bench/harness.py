"""Run one cell of the benchmark once; see ``run.py`` for the command.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the names in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the deployment.  Its ``service`` names
  ``bench/services/<service>.py``, the part of the program a request drives
  (``setup``, ``request``, ``observe``, ``close``, ``check``), and its
  ``graph.generator`` names ``bench/graphs/<generator>.py``;
- ``bench/traffic/<traffic>.json``: the parameters of the mix.  Its
  ``client`` names ``bench/clients/<client>.py``, the loop that sends the
  requests (``warm_up``, ``window``), and its ``generator`` names
  ``bench/generators/<generator>.py``, whose ``make(run)`` returns the
  requests (``request(i)``);
- ``bench/metrics/<metric>.py``: a ``read(run)`` that returns the metric's
  value from the run (end-to-end metrics) or the traced run (per-layer
  metrics), or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(BENCH, ".cache", "jax")

#: JAX's monitoring events that mark a program being lowered or compiled
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclass
class Run:
    """One run of one cell: its inputs, what the window saw, and what the
    traced run recorded."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    program: object
    device_kind: str = ""
    graph: object = None
    base: object = None
    perm: object = None
    requests: object = None
    attempted: int = 0
    failed: int = 0
    setup_s: float = 0.0
    reference_s: float = 0.0
    window_s: float = 0.0
    peak_bytes: int | None = None
    units: list = field(default_factory=list)
    # traced run only: device operations, window and host spans on the
    # profiler's clock (ns), and the program's counter deltas
    ops: list = field(default_factory=list)
    window_ns: tuple = (0.0, 0.0)
    spans: list = field(default_factory=list)
    program_spans: list = field(default_factory=list)
    registry_delta: dict = field(default_factory=dict)

    def fail(self, exc: BaseException) -> None:
        self.failed += 1
        traceback.print_exception(exc, file=sys.stderr)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """The module in the file ``path``."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"no file {os.path.relpath(path, ROOT)}")
    name = "bench_" + os.path.relpath(path, BENCH)[:-3].replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str) -> tuple:
    """``(cell, configuration, traffic)`` of a cell, the last two read from
    their files."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def applies(metric: dict, cell: dict, e2e_names=None) -> bool:
    """Whether a cell reports a metric: the cells its ``workloads`` list, or
    without that key every cell (end-to-end) or every cell that reports the
    end-to-end metric it ``moves`` (per-layer, given ``e2e_names``)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def device_check(chips: int):
    """The accelerator devices, or ``NoDevice``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX lowerings and backend compiles from its monitoring
    events."""

    def __init__(self):
        import jax

        self.lowerings = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == LOWERING:
            self.lowerings += 1
        elif event == BACKEND_COMPILE:
            self.compiles += 1

    def read(self) -> tuple:
        return self.lowerings, self.compiles


class FullCollections:
    """Counts the interpreter's full (generation 2) garbage collections and
    the seconds they take."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def read(self) -> tuple:
        return self.count, self.seconds


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, so that only a checkout's first run compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _traced_window(run, client, service, log_dir) -> None:
    """The window under the JAX profiler and the program's span collector;
    fills the run's trace fields."""
    import jax

    from bench import devtrace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    reg0 = run.program.registry()
    t_spans = time.perf_counter()
    run.program.start_spans()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            client.window(run, service)
    finally:
        jax.profiler.stop_trace()
        spans = run.program.stop_spans()
    reg1 = run.program.registry()
    run.registry_delta = {k: v - reg0.get(k, 0.0) for k, v in reg1.items()}
    events = devtrace.read_xspace(log_dir)
    marks = [e for e in events if e.name == "bench.window"]
    if len(marks) != 1:
        raise RuntimeError(f"{len(marks)} window marks in the trace")
    lo, hi = marks[0].start_ns, marks[0].end_ns
    offset = lo - t_w0 * 1e9  # perf_counter seconds -> trace ns
    run.window_ns = (lo, hi)
    run.ops = devtrace.device_ops(events)
    run.program_spans = [
        ((t_spans + e["ts"] / 1e6) * 1e9 + offset,
         (t_spans + (e["ts"] + e["dur"]) / 1e6) * 1e9 + offset, e["name"])
        for e in spans]
    run.spans = run.program_spans + [
        (u["start"] * 1e9 + offset, u["end"] * 1e9 + offset, u["name"])
        for u in run.units]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, program=None, require_tpu: bool = True,
             overrides: dict | None = None) -> tuple:
    """Run one cell once.  Returns ``(result, checks)``: the result line's
    object and the numbers compared, ``(name, value, op, limit)``.

    ``program``, ``require_tpu`` and ``overrides`` (keys of the
    configuration) are for tests, which drive the harness on the CPU at a
    small size and with the timed path broken."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = cell_spec(bench, workload)
    config = {**config, **(overrides or {})}
    if ROOT + "/src" not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devs = device_check(cell["chips"]) if require_tpu else jax.devices()
    enable_compile_cache()
    counter = CompileCounter()
    collections = FullCollections()

    from bench import graph
    from bench.program import Program

    def part(kind, name):
        return load_module(os.path.join(BENCH, kind, name + ".py"))

    service = part("services", config["service"])
    client = part("clients", traffic["client"])
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, program=program or Program(config),
              device_kind=devs[0].device_kind)
    run.base, run.perm, run.graph = graph.make_graph(
        config, seed, part("graphs", config["graph"]["generator"]))
    run.requests = part("generators", traffic["generator"]).make(run)
    service.setup(run)
    client.warm_up(run, service)
    # the set-up's objects stay out of the window's full collections
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - started - run.reference_s

    c0, g0 = counter.read(), collections.read()
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            _traced_window(run, client, service, log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        client.window(run, service)
    c1, g1 = counter.read(), collections.read()
    gc.unfreeze()
    print(f"setup_s={run.setup_s:.3f} (reference {run.reference_s:.3f} s "
          f"left out); window {run.window_s:.3f} s, units (seconds/passes): "
          + " ".join(f"{u['end'] - u['start']:.3f}/{u['passes']}"
                     for u in run.units), file=sys.stderr)
    print(f"compilations in the window: lowerings={c1[0] - c0[0]} "
          f"backend_compiles={c1[1] - c0[1]}; full collections: "
          f"{g1[0] - g0[0]}, {g1[1] - g0[1]:.3f} s", file=sys.stderr,
          flush=True)

    stats = devs[0].memory_stats() or {}
    run.peak_bytes = stats.get("peak_bytes_in_use")
    service.close(run)

    e2e_names = [m["name"] for m in bench["end_to_end"] if applies(m, cell)]
    metrics = {}
    for m in bench["per_layer"] if trace else bench["end_to_end"]:
        if applies(m, cell, e2e_names if trace else None):
            value = part("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = service.check(run)
    ok = run.failed == 0 and all(
        (v <= lim) if op == "<=" else (v >= lim) for _, v, op, lim in checks)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.peak_bytes}
    result = {"correct": ok, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        from bench import devtrace

        lo, hi = run.window_ns
        device.update(busy_s=devtrace.busy_ns(run.ops, lo, hi) / 1e9,
                      window_s=(hi - lo) / 1e9)
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(run.ops),
            "idle_gaps": devtrace.top_gaps(devtrace.attribute(
                devtrace.gaps(run.ops, lo, hi), run.spans)),
        }
    result["checks"] = {name: {"value": v, "limit": f"{op} {lim}"}
                        for name, v, op, lim in checks}
    return result, checks


def main(argv, started: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once; print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src: run from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), started=started)
    except NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    for name, v, op, lim in checks:
        print(f"check {name}: {v} (limit {op} {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

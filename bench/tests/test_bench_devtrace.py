"""The reduction from a profiler trace to busy time, program time and
attributed idle gaps."""
import pytest

from bench import devtrace
from bench.devtrace import Event

TPU = "/device:TPU:0"


def tpu_trace():
    """Two programs on one chip between t=0 and t=100 ns:
    jit_chunk runs ops over [10, 30] and [35, 50], jit_other over [60, 70]."""
    return [
        Event(TPU, "XLA Modules", "jit_chunk(3)", 10, 40),
        Event(TPU, "XLA Ops", "fusion.1", 10, 15),
        Event(TPU, "XLA Ops", "gather.2", 25, 5),
        Event(TPU, "XLA Ops", "fusion.1", 35, 15),
        Event(TPU, "XLA Modules", "jit_other(7)", 60, 10),
        Event(TPU, "XLA Ops", "copy", 60, 10),
        Event("/host:CPU", "python", "bench.window", 0, 100),
    ]


def test_device_ops_take_their_program_from_the_modules_line():
    ops = devtrace.device_ops(tpu_trace())
    assert [e.stats["hlo_module"] for e in ops] == [
        "jit_chunk", "jit_chunk", "jit_chunk", "jit_other"]
    assert devtrace.module_ns(ops, "jit_chunk") == 35
    assert devtrace.module_ns(ops, "jit_other") == 10
    assert devtrace.top_ops(ops, 2) == [["jit_chunk/fusion.1", 30e-9],
                                        ["jit_other/copy", 10e-9]]


def test_top_ops_list_leaves_by_short_name():
    ops = devtrace.device_ops([
        Event(TPU, "XLA Modules", "jit_chunk(3)", 0, 100),
        Event(TPU, "XLA Ops", "%while.4 = (s32[]) while(s32[] %x)", 0, 90),
        Event(TPU, "XLA Ops", "%fusion.25 = s32[8] fusion(s32[8] %a)", 5, 40),
        Event(TPU, "XLA Ops", "%fusion.25 = s32[8] fusion(s32[8] %a)", 50, 30),
        Event(TPU, "XLA Ops", "%copy.1 = s32[8] copy(s32[8] %b)", 92, 5),
    ])
    assert devtrace.top_ops(ops) == [["jit_chunk/fusion.25", 70e-9],
                                     ["jit_chunk/copy.1", 5e-9]]
    assert devtrace.busy_ns(ops, 0, 100) == 95


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = devtrace.device_ops(tpu_trace())
    assert devtrace.busy_ns(ops, 0, 100) == 20 + 15 + 10
    assert devtrace.busy_ns(ops, 25, 65) == 5 + 15 + 5
    assert devtrace.gaps(ops, 0, 100) == [(0, 10), (30, 35), (50, 60),
                                          (70, 100)]


def test_busy_averages_over_chips():
    ops = devtrace.device_ops(tpu_trace() + [
        Event("/device:TPU:1", "XLA Ops", "fusion.1", 0, 100)])
    assert devtrace.chips(ops) == 2
    assert devtrace.busy_ns(ops, 0, 100) == (45 + 100) / 2


def test_gaps_go_to_the_innermost_host_span():
    gaps = [(0, 10), (30, 35), (50, 60), (70, 100)]
    spans = [(0, 100, "bench.decompose"), (28, 62, "resident.chunk"),
             (49, 61, "replay")]
    assert devtrace.attribute(gaps, spans) == {
        "bench.decompose": 40, "resident.chunk": 5, "replay": 10}
    assert devtrace.attribute([(200, 210)], spans) == {"no host span": 10}
    assert devtrace.top_gaps({"a": 5e9, "b": 7e9}, 1) == [["b", 7.0]]


def test_merge_and_clip():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                                  (5, 9)]
    assert devtrace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_a_recorded_cpu_trace_reads_back(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chunk(x):
        return jnp.cumsum(x * 2) + 1

    x = jnp.ones(200_000)
    chunk(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            chunk(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.read_xspace(str(tmp_path))
    (mark,) = [e for e in events if e.name == "bench.window"]
    ops = devtrace.device_ops(events)
    assert ops and all(e.stats["hlo_module"] == "jit_chunk" for e in ops)
    busy = devtrace.busy_ns(ops, mark.start_ns, mark.end_ns)
    assert 0 < busy <= mark.dur_ns
    assert devtrace.module_ns(ops, "jit_chunk") > 0


def test_two_traces_in_one_directory_are_refused(tmp_path):
    for d in ("a", "b"):
        (tmp_path / d).mkdir()
        (tmp_path / d / "x.xplane.pb").write_bytes(b"")
    with pytest.raises(RuntimeError):
        devtrace.read_xspace(str(tmp_path))

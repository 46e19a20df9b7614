"""The harness, driven on the CPU at a small size past its look for a chip:
sound runs come out correct, and each fault planted under the timed path,
and the control, come out not correct."""
import numpy as np
import pytest

from bench import control, harness
from bench.program import Ack, Program

TINY = {"nodes": 3000, "edges": 40000}
DECOMPOSE = "lj-decompose.semicore-star"
MIXED = "lj-maintain.mixed-b64"
EXPIRE = "lj-maintain.expire-b64"


@pytest.fixture(autouse=True)
def cache_in_tmp(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR",
                        str(tmp_path_factory.getbasetemp() / "jax-cache"))


def run(cell, program_cls=None, seconds=0.5, trace=False, seed=2**31 + 5):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, _ = harness.cell_spec(bench, cell)
    program = program_cls({**config, **TINY}) if program_cls else None
    result, _ = harness.run_cell(cell, seed, seconds, trace, started=0.0,
                                 program=program, require_tpu=False,
                                 overrides=TINY)
    return result


# ------------------------------------------------------------ decompose
class DecomposeUnchanged(Program):
    """The fixpoint returns its starting state."""

    def decompose(self, g):
        r = super().decompose(g)
        r.core = g.degrees().astype(np.int64)
        r.cnt = np.zeros_like(r.cnt)
        return r


class DecomposeHalf(Program):
    """Half of the edge table left out."""

    def decompose(self, g):
        from bench.graph import Graph

        lo, hi = g.pairs()
        half = Graph.from_pairs(g.n, lo[::2], hi[::2])
        return super().decompose(half)


class DecomposeAltered(Program):
    """One core number altered where it is produced."""

    def decompose(self, g):
        r = super().decompose(g)
        r.core = r.core.copy()
        r.core[int(np.argmax(r.core))] += 1
        return r


# ------------------------------------------------------------ writer
class WriterProgram(Program):
    wrap = None

    def open_writer(self, g, core, cnt, wal_path):
        return self.wrap(super().open_writer(g, core, cnt, wal_path))


class Unchanged:
    """Every batch is logged and acknowledged, but the state never moves."""

    def __init__(self, w):
        self.w = w
        self.frozen = None

    def ingest(self, ops):
        if self.frozen is None:
            self.frozen = tuple(a.copy() for a in self.w.state())
        self.w._w.wal.append(self.w._w.epoch + 1, _batch(ops))
        self.w._w.epoch += 1
        return Ack(passes=0)

    def state(self):
        return self.frozen

    def coreness(self, nodes):
        return self.frozen[0][nodes]

    def top_k(self, k):
        return self.w.top_k(k)

    def degeneracy(self):
        return self.w.degeneracy()

    def close(self):
        self.w.close()


def _batch(ops):
    from repro.core.update import UpdateBatch

    return UpdateBatch.from_wire([list(o) for o in ops])


class Half:
    """Half of each batch left out."""

    def __init__(self, w):
        self.w = w

    def __getattr__(self, name):
        return getattr(self.w, name)

    def ingest(self, ops):
        return self.w.ingest(ops[: len(ops) // 2])


class AlteredReply:
    """The coreness reply altered where it is produced."""

    def __init__(self, w):
        self.w = w

    def __getattr__(self, name):
        return getattr(self.w, name)

    def coreness(self, nodes):
        out = np.array(self.w.coreness(nodes))
        out[0] += 1
        return out


class OneStale:
    """One batch of the window, not the last, publishes the state of the
    batch before."""

    def __init__(self, w):
        self.w = w
        self.seen = 0
        self.before = None

    def __getattr__(self, name):
        return getattr(self.w, name)

    def ingest(self, ops):
        self.before = tuple(a.copy() for a in self.w.state())
        self.seen += 1
        return self.w.ingest(ops)

    def state(self):
        return self.before if self.seen == 3 else self.w.state()


class LostLog:
    """The log loses every record after it is written."""

    def __init__(self, w):
        self.w = w

    def __getattr__(self, name):
        return getattr(self.w, name)

    def ingest(self, ops):
        ack = self.w.ingest(ops)
        log = self.w._w.wal
        log._f.truncate(0)
        return ack


def writer_fault(wrap):
    return type(f"Writer{wrap.__name__}", (WriterProgram,), {"wrap": wrap})


# ------------------------------------------------------------ tests
@pytest.mark.parametrize("cell", [DECOMPOSE, MIXED, EXPIRE])
def test_a_sound_run_is_correct(cell):
    result = run(cell)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert "setup_s" in result["metrics"]
    checked = result["checks"].get("batches_checked")
    if checked:  # every answer of the window is checked
        assert checked["value"] == result["attempted"] >= 3


@pytest.mark.parametrize("cell", [DECOMPOSE, MIXED, EXPIRE])
def test_a_traced_run_reports_its_per_layer_metrics(cell, monkeypatch):
    from bench import readings

    peak = readings.peak
    monkeypatch.setattr(readings, "peak",
                        lambda kind, what: peak("TPU v5 lite", what))
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    want = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    result = run(cell, trace=True)
    assert result["correct"]
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert result["breakdown"]["device_ops"]


@pytest.mark.parametrize("fault", [DecomposeUnchanged, DecomposeHalf,
                                   DecomposeAltered, control.ControlProgram])
def test_decompose_faults_are_not_correct(fault):
    assert run(DECOMPOSE, fault)["correct"] is False


@pytest.mark.parametrize("cell", [MIXED, EXPIRE])
@pytest.mark.parametrize("wrap", [Unchanged, Half, AlteredReply, OneStale,
                                  LostLog])
def test_writer_faults_are_not_correct(cell, wrap):
    assert run(cell, writer_fault(wrap))["correct"] is False


@pytest.mark.parametrize("cell", [MIXED, EXPIRE])
def test_writer_control_is_not_correct(cell):
    assert run(cell, control.ControlProgram)["correct"] is False


def test_a_failing_batch_counts_and_is_not_correct():
    class Refuses(Half):
        """Acknowledges the warm-up batch, then refuses."""

        def ingest(self, ops):
            if self.w._w.epoch:
                raise RuntimeError("refused")
            return self.w.ingest(ops)

    result = run(MIXED, writer_fault(Refuses))
    assert result["failed"] == 1 and result["correct"] is False

"""One streaming writer: each request is a batch of edge updates, answered
once ``ingest`` has returned (logged, settled and published), followed by
the queries the traffic names.

Set-up computes the starting state (core, and cnt by its definition) with
the reference, as a writer restarting from a snapshot would load it; that
time is the reference's and is left out of ``setup_s``.  The writer's log
lies under ``$TMPDIR``.

Afterwards every answer of the window is checked against the reference run
on the graph with every op acknowledged up to it applied (the writer's core
and cnt, and the query replies), and the log is read back: every
acknowledged batch, in order.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from bench.graph import apply_ops
from bench.reference import cores, wal

UNIT = "bench.ingest"


def setup(run) -> None:
    g = run.graph
    t0 = time.perf_counter()
    core = cores.peel(g)
    cnt = cores.cnt(g, core)
    run.reference_s += time.perf_counter() - t0
    run.wal_dir = tempfile.mkdtemp(prefix="bench-wal-")
    run.wal_path = os.path.join(run.wal_dir, "writer.wal")
    run.writer = run.program.open_writer(g, core, cnt, run.wal_path)
    run.acked = []
    run.replies = []


def request(run, ops) -> dict:
    ack = run.writer.ingest(ops)
    run.acked.append(ops)
    touched = np.unique(np.asarray([[u, v] for _, u, v in ops]))
    q = run.traffic.get("queries", {})
    return {
        "passes": ack.passes,
        "ops": len(ops),
        "touched": touched,
        "coreness": (run.writer.coreness(touched)
                     if q.get("coreness_of_touched") else None),
        "top_k": run.writer.top_k(q["top_k"]) if q.get("top_k") else None,
        "degeneracy": run.writer.degeneracy() if q.get("degeneracy") else None,
    }


def observe(run, reply) -> None:
    """Keeps the published state beside the answer, for the check."""
    core, cnt = run.writer.state()
    reply.update(batch=len(run.acked) - 1, core=core.astype(np.int32),
                 cnt=cnt.astype(np.int32))
    run.replies.append(reply)


def close(run) -> None:
    run.writer.close()


def check(run) -> list:
    """``(name, value, op, limit)`` of each number compared."""
    core_bad = cnt_bad = query_bad = 0
    g, done = run.graph, 0
    for r in run.replies:
        g = apply_ops(g, run.acked[done:r["batch"] + 1])
        done = r["batch"] + 1
        core = cores.peel(g)
        core_bad += int((r["core"] != core).sum())
        cnt_bad += int((r["cnt"] != cores.cnt(g, core)).sum())
        if r["coreness"] is not None:
            query_bad += int((np.asarray(r["coreness"])
                              != core[r["touched"]]).sum())
        if r["top_k"] is not None:
            want = cores.top_k(core, len(r["top_k"]))
            query_bad += int(not np.array_equal(r["top_k"], want))
        if r["degeneracy"] is not None:
            query_bad += int(r["degeneracy"] != int(core.max()))
    wal_bad = wal.missing_batches(run.wal_path, run.acked)
    shutil.rmtree(run.wal_dir, ignore_errors=True)
    return [
        ("batches_checked", len(run.replies), ">=", 1),
        ("core_mismatch_nodes", core_bad, "<=", 0),
        ("cnt_mismatch_nodes", cnt_bad, "<=", 0),
        ("query_mismatches", query_bad, "<=", 0),
        ("wal_batches_missing", wal_bad, "<=", 0),
    ]

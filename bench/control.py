"""The control of ``correct``: the reference in the program's place,
computed as a tempting shortcut would, which must come out as not correct.

The configurations state no precision; the control breaks the guarantee
they state, exact core numbers:

- decompose: SemiCore* stopped one pass before its fixpoint, as a program
  that ends the fixpoint when few nodes still change;
- writer: each batch acknowledged before it is settled, so the published
  state is that of the batch before (the log is still written in full).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

runs the harness with the control in the program's place, once per seed in
one process, and prints each seed's numbers compared.  The benchmark's own
runs never run it.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.graph import apply_ops  # noqa: E402
from bench.program import Ack, Decomposition, Program  # noqa: E402
from bench.reference import cores, wal, work  # noqa: E402


class ControlWriter:
    def __init__(self, g, core, cnt, wal_path):
        self.g = g
        self.published = (np.asarray(core), np.asarray(cnt))
        self.latest = self.published
        self.epoch = 0
        self.log = open(wal_path, "ab")

    def ingest(self, ops) -> Ack:
        self.epoch += 1
        self.log.write(wal.frame(self.epoch, ops))
        self.log.flush()
        os.fsync(self.log.fileno())
        self.g = apply_ops(self.g, [ops])
        core = cores.peel(self.g)
        self.published, self.latest = self.latest, (core,
                                                    cores.cnt(self.g, core))
        return Ack(passes=0)

    def state(self):
        return self.published

    def coreness(self, nodes):
        return self.published[0][nodes]

    def top_k(self, k):
        return cores.top_k(self.published[0], k)

    def degeneracy(self):
        return int(self.published[0].max())

    def close(self):
        self.log.close()


class ControlProgram(Program):
    def decompose(self, g) -> Decomposition:
        _, frontiers = work.semicore_star(g)
        core, _ = work.semicore_star(g, max_passes=len(frontiers) - 1)
        return Decomposition(core, cores.cnt(g, core), len(frontiers) - 1)

    def open_writer(self, g, core, cnt, wal_path):
        return ControlWriter(g, core, cnt, wal_path)


def main(argv) -> int:
    import argparse

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
        _, config, _ = harness.cell_spec(bench, args.workload)
        result, checks = harness.run_cell(
            args.workload, seed, args.seconds, False,
            started=time.perf_counter(), program=ControlProgram(config))
        print(f"control {args.workload} seed={seed} "
              f"correct={result['correct']} " + " ".join(
                  f"{n}={v}" for n, v, _, _ in checks), flush=True)
        failed_all &= not result["correct"]
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

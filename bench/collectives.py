"""Device time of the collectives in a traced run: the all_gathers and
psums that a sharded program runs between its chips.

An operation is a collective by its HLO name (``all-reduce.15``,
``all-gather-start.2``, ``psum.6``; the CPU spells ``all_gather.4``).  The
TPU compiler may lower an ``all_gather`` as a dynamic-update-slice and an
``all-reduce``, so every kind counts.  A collective contains no other
operation, so each is a leaf of the trace.
"""
from __future__ import annotations

import re

from bench import devtrace

_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|all-to-all|reduce-scatter"
                         r"|collective-permute|collective-broadcast|psum)")


def is_collective(e) -> bool:
    name = e.name.split(" = ")[0].lstrip("%").replace("_", "-")
    return bool(_COLLECTIVE.match(name))


def collective_ns(ops, prefix: str) -> float:
    """Device time of the collectives of programs whose name starts with
    ``prefix``: the union of their intervals on each device plane, averaged
    over the planes that ran those programs."""
    mine = [e for e in ops
            if str(e.stats.get("hlo_module", "")).startswith(prefix)]
    coll = [e for e in mine if is_collective(e)]
    if not coll:
        return 0.0
    return (devtrace.busy_ns(coll, float("-inf"), float("inf"))
            * devtrace.chips(coll) / devtrace.chips(mine))

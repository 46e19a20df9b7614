"""Readings that several metrics share.  Each returns ``None``
where the traced run holds nothing to read."""
from __future__ import annotations

import json
import os

from bench import devtrace

#: the resident fixpoint's chunk program: a ``lax.scan`` of passes
CHUNK_PROGRAM = "jit_chunk"


def idle_pct(run):
    """Share of the window in which no operation ran on the device."""
    lo, hi = run.window_ns
    if not run.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(run.ops, lo, hi) / (hi - lo))


def ops_per_s(run):
    """Ops of the window's answered requests over the window's time."""
    if not run.units or run.window_s <= 0:
        return None
    return sum(u["ops"] for u in run.units) / run.window_s


def passes(run) -> int:
    """Fixpoint passes the window's units ran, as the program counts them."""
    return sum(u["passes"] for u in run.units)


def chunk_ns(run) -> float:
    """Device time of the fixpoint's chunk program in the window."""
    lo, hi = run.window_ns
    return devtrace.module_ns(
        [e for e in run.ops if lo <= e.start_ns <= hi], CHUNK_PROGRAM)


def fixpoint_ms_per_pass(run):
    ns, p = chunk_ns(run), passes(run)
    if not ns or not p:
        return None
    return ns / p / 1e6


def histogram_mean_ms(run, family: str):
    """Mean of a program histogram over the window, from its counter
    deltas (``<family>_sum`` and ``<family>_count``, any labels)."""
    total = count = 0.0
    for key, v in run.registry_delta.items():
        if key.split("{")[0] == family + "_sum":
            total += v
        elif key.split("{")[0] == family + "_count":
            count += v
    if not count:
        return None
    return 1e3 * total / count


def peak(device_kind: str, what: str) -> float:
    """A published peak of the device (``bench/peaks.json``); a device not in
    the table is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return float(table["devices"][device_kind][what])

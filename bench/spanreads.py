"""Readings of the program's own spans and counters that several per-layer
metrics share.  Each returns ``None`` where the traced run holds nothing to
read: a program without the span or the counter."""
from __future__ import annotations

from bench import devtrace


def intervals(run, name: str) -> list:
    """``(start_ns, end_ns)`` of the program's spans called ``name``."""
    return [(s, e) for s, e, nm in run.program_spans if nm == name]


def per_unit_ms(run, name: str):
    """Summed time of the ``name`` spans over the window's units, in ms."""
    spans = intervals(run, name)
    if not spans or not run.units:
        return None
    return sum(e - s for s, e in spans) / len(run.units) / 1e6


def uncovered_per_unit_ms(run, outer: str, inner: str):
    """Time inside the ``outer`` spans that no ``inner`` span covers, over
    the window's units, in ms."""
    outers = intervals(run, outer)
    if not outers or not run.units:
        return None
    inners = devtrace.merge(intervals(run, inner))
    total = sum((e - s) - sum(b - a for a, b in devtrace.clip(inners, s, e))
                for s, e in outers)
    return total / len(run.units) / 1e6


def counter_delta(run, family: str):
    """The window's delta of a program counter, summed over its labels."""
    values = [v for k, v in run.registry_delta.items()
              if k.split("{")[0] == family]
    return sum(values) if values else None

"""Reduce a profiler trace to device busy time, per-program device time and
idle gaps, and attribute each gap to the host span open at the time.

The JAX profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it into planes, lines and events (start and duration in ns).  A device
operation is an event on the ``XLA Ops`` line of a ``/device:TPU:<i>``
plane, or, where there is no such plane (the CPU, in tests), any event that
names its ``hlo_op``.  Its program is its ``hlo_module`` stat, else the
``XLA Modules`` event of its plane that contains it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xspace(log_dir: str) -> list:
    """Every event of the one trace written under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 dict(e.stats)))
    return out


def device_ops(events) -> list:
    """The device operations, each with ``stats["hlo_module"]`` set."""
    tpu = [e for e in events if _DEVICE_PLANE.match(e.plane)]
    if not tpu:
        return [e for e in events if "hlo_op" in e.stats and e.dur_ns > 0]
    modules: dict = {}
    for e in tpu:
        if e.line == "XLA Modules":
            modules.setdefault(e.plane, []).append(e)
    for v in modules.values():
        v.sort(key=lambda e: e.start_ns)
    ops = [e for e in tpu if e.line == "XLA Ops" and e.dur_ns > 0]
    for op in ops:
        if "hlo_module" not in op.stats:
            op.stats["hlo_module"] = _containing(modules.get(op.plane, ()),
                                                 op.start_ns)
    return ops


def _containing(spans, t) -> str:
    lo, hi = 0, len(spans)
    while lo < hi:  # last span starting at or before t
        mid = (lo + hi) // 2
        if spans[mid].start_ns <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and spans[lo - 1].end_ns >= t:
        return _MODULE_SUFFIX.sub("", spans[lo - 1].name)
    return "unknown"


def chips(ops) -> int:
    """Number of device planes the operations ran on (at least 1)."""
    return max(1, len({e.plane for e in ops}))


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def busy_ns(ops, lo, hi) -> float:
    """Time in ``[lo, hi]`` in which an operation ran, averaged over the
    device planes."""
    per_plane: dict = {}
    for e in ops:
        per_plane.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    if not per_plane:
        return 0.0
    total = sum(sum(b - a for a, b in clip(merge(iv), lo, hi))
                for iv in per_plane.values())
    return total / len(per_plane)


def gaps(ops, lo, hi) -> list:
    """Idle ``(start, end)`` stretches in ``[lo, hi]`` of the first device."""
    if not ops:
        return [(lo, hi)]
    first = min(e.plane for e in ops)
    busy = clip(merge((e.start_ns, e.end_ns) for e in ops
                      if e.plane == first), lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def module_ns(ops, prefix: str) -> float:
    """Device time of programs whose name starts with ``prefix``: the union
    of their operations' intervals (an operation nested in another counts
    once), averaged over the device planes."""
    mine = [e for e in ops
            if str(e.stats.get("hlo_module", "")).startswith(prefix)]
    return busy_ns(mine, float("-inf"), float("inf")) * (
        chips(mine) / chips(ops) if mine else 0.0)


def leaves(ops) -> list:
    """The operations that contain no other operation of their plane (a
    loop or conditional is listed over the operations of its body)."""
    out = []
    for plane in {e.plane for e in ops}:
        mine = sorted((e for e in ops if e.plane == plane),
                      key=lambda e: (e.start_ns, -e.dur_ns))
        for a, b in zip(mine, mine[1:]):
            if b.start_ns >= a.end_ns:
                out.append(a)
        out.extend(mine[-1:])
    return out


def op_name(e) -> str:
    """``program/op``: the op's HLO name without its signature."""
    name = e.name.split(" = ")[0].lstrip("%")
    return f"{e.stats.get('hlo_module', 'unknown')}/{name}"


def top_ops(ops, k: int = 10) -> list:
    """``[[program/op, seconds], ...]``: the leaf operations that took most
    device time, summed by name."""
    by: dict = {}
    for e in leaves(ops):
        by[op_name(e)] = by.get(op_name(e), 0.0) + e.dur_ns
    n = chips(ops)
    return [[name, ns / n / 1e9]
            for name, ns in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def attribute(gap_list, spans) -> dict:
    """Idle ns per name of the innermost host span that contains each gap's
    middle (``spans``: ``(start_ns, end_ns, name)`` on the trace's clock)."""
    import numpy as np

    if not gap_list:
        return {}
    g = np.asarray(gap_list, dtype=np.float64)
    order = np.argsort(g.sum(axis=1))
    mids = g.sum(axis=1)[order] / 2
    best = np.full(len(g), np.inf)
    owner = np.full(len(g), -1)
    names = []
    for i, (s, e, nm) in enumerate(spans):
        names.append(nm)
        sel = slice(np.searchsorted(mids, s, side="left"),
                    np.searchsorted(mids, e, side="right"))
        shorter = (e - s) < best[sel]
        best[sel] = np.where(shorter, e - s, best[sel])
        owner[sel] = np.where(shorter, i, owner[sel])
    out: dict = {}
    for j, idx in enumerate(order):
        name = names[owner[j]] if owner[j] >= 0 else "no host span"
        out[name] = out.get(name, 0.0) + float(g[idx, 1] - g[idx, 0])
    return out


def top_gaps(by_name: dict, k: int = 10) -> list:
    return [[name, ns / 1e9] for name, ns in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:k]]

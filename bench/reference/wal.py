"""Read the writer's write-ahead log back, independently of the program.

Each record is one line ``c1 <len> <crc32c-hex8> <payload>``: the payload is
JSON ``{"epoch": e, "ops": [[kind, u, v], ...]}`` and the checksum CRC32C
(Castagnoli) of the payload bytes.  A record that fails its length or
checksum reads as ``None``.
"""
from __future__ import annotations

import json

_POLY = 0x82F63B78


def _table():
    out = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        out.append(c)
    return out


_TABLE = _table()


def crc32c(data: bytes) -> int:
    c = 0xFFFFFFFF
    for byte in data:
        c = _TABLE[(c ^ byte) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def frame(epoch: int, ops) -> bytes:
    """One record, as the writer frames it."""
    payload = json.dumps({"epoch": int(epoch), "ops": [list(o) for o in ops]},
                         separators=(",", ":")).encode()
    return b"c1 %d %08x %s\n" % (len(payload), crc32c(payload), payload)


def parse_line(line: bytes):
    """``(epoch, ops)`` of one record, or ``None`` where it is damaged."""
    parts = line.rstrip(b"\n").split(b" ", 3)
    if len(parts) != 4 or parts[0] != b"c1":
        return None
    try:
        length, crc = int(parts[1]), int(parts[2], 16)
    except ValueError:
        return None
    payload = parts[3]
    if len(payload) != length or crc32c(payload) != crc:
        return None
    rec = json.loads(payload)
    return int(rec["epoch"]), [(k, int(u), int(v)) for k, u, v in rec["ops"]]


def read(path: str) -> list:
    """Every record of the log, in file order."""
    with open(path, "rb") as f:
        return [parse_line(line) for line in f if line.strip()]


def canonical(ops) -> list:
    """A batch as the set of edge changes it asks for, sorted."""
    return sorted((k, min(u, v), max(u, v)) for k, u, v in ops)


def missing_batches(path: str, acked: list) -> int:
    """How many acknowledged batches the log does not hold, intact and in
    order: record i must carry epoch i + 1 and the ops of ``acked[i]``."""
    records = read(path)
    bad = 0
    for i, ops in enumerate(acked):
        rec = records[i] if i < len(records) else None
        if rec is None or rec[0] != i + 1 or canonical(rec[1]) != canonical(ops):
            bad += 1
    return bad

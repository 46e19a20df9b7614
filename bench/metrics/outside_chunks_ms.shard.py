"""Per decompose, the wall time outside the program's ``resident.chunk``
spans: binding, the sharded layout and its upload, and the result."""


def read(run):
    units = [s for s in run.spans if s[2] == "bench.decompose"]
    chunks = [s for s in run.program_spans if s[2] == "resident.chunk"]
    if not units or not chunks:
        return None
    outside = []
    for s, e, _ in units:
        inside = sum(b - a for a, b, _ in chunks if s <= a and b <= e)
        outside.append((e - s) - inside)
    return sum(outside) / len(outside) / 1e6

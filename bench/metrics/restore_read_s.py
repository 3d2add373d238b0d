"""Mean ReadResult.duration of the window's restores: the aggregated
read of the PFS files."""


def read(rec):
    cycles = [c for c in rec.get("restores", []) if c["read_s"] is not None]
    if not cycles:
        return None
    return sum(c["read_s"] for c in cycles) / len(cycles)

"""Bytes the window's flushes wrote to the PFS level over their summed
FlushResult.duration, in GB/s."""


def read(rec):
    done = [s for s in rec.get("saves", []) if s["flush_s"]]
    if not done:
        return None
    return sum(s["flush_bytes"] for s in done) / sum(s["flush_s"] for s in done) / 1e9

"""From a fresh CheckpointManager to the first completed train step on the
state restored from the PFS level and placed on the mesh, over the cycles."""


def read(rec):
    cycles = rec.get("restores")
    if not cycles:
        return None
    return sum(c["resume_s"] for c in cycles) / len(cycles)

"""Mean seconds to put the restored host state onto the mesh
(launch/train.place_state to block_until_ready)."""


def read(rec):
    cycles = rec.get("restores")
    if not cycles:
        return None
    return sum(c["place_s"] for c in cycles) / len(cycles)

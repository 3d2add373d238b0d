"""From each save() call to its step reaching flush_done on the PFS level,
summed over the saves that landed, over their number."""


def read(rec):
    landed = [s for s in rec.get("saves", []) if s["durable_at"] is not None]
    if not landed:
        return None
    return sum(s["durable_at"] - s["t_call"] for s in landed) / len(landed)

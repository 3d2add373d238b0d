"""Mean time a save's flush waited: from save() returning to its step
reaching flush_done, less the flush's own duration."""


def read(rec):
    done = [s for s in rec.get("saves", [])
            if s["flush_s"] is not None and s["durable_at"] is not None]
    if not done:
        return None
    return sum(s["durable_at"] - s["t_return"] - s["flush_s"] for s in done) / len(done)

"""Wall time of the window's save() calls over their number."""


def read(rec):
    saves = rec.get("saves")
    if not saves:
        return None
    return sum(s["t_return"] - s["t_call"] for s in saves) / len(saves)

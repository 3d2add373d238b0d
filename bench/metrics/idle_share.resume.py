"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips (bench/trace.py)."""


def read(rec):
    tr = rec.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]

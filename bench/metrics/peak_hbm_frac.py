"""peak_bytes_in_use over bytes_limit on the fullest chip, in %, read
after the window and before the reference runs."""


def read(rec):
    if not rec.get("bytes_limit"):
        return None
    return 100.0 * rec["peak_bytes"] / rec["bytes_limit"]

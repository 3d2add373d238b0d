"""Tokens trained in the window over the window's wall time: every save's
stall and any slowdown the background flush causes are inside it."""


def read(rec):
    if "tokens" not in rec or rec["window_s"] <= 0:
        return None
    return rec["tokens"] / rec["window_s"]

"""Seconds from the process's start to the opening of the window: start-up,
compilation (or the compile cache), weights, the first steps, warm-up."""


def read(rec):
    return rec.get("setup_s")

"""Mean seconds a save's flush waits in the queue: its ckpt.flush.queued
span, from the put in save() to the flush worker's get."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.flush.queued"))

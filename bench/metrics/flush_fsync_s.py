"""Mean seconds of a flush's ckpt.flush.fsync span: the final fsync of
the PFS files the flush wrote."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.flush.fsync"))

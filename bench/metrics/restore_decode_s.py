"""Mean seconds of a restore's ckpt.restore.decode span: rank blobs
decoded into the stream and assembled into leaves."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.restore", "restores"), "ckpt.restore.decode"))

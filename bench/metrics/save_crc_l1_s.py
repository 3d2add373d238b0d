"""Mean wall seconds of a save's ckpt.save.ranks span: the per-rank
tasks (encode, CRC and L1 write of each rank) on the local pool."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.save.ranks"))

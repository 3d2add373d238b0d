"""Mean seconds a save spends in its ckpt.save.copy span: the copies of
the host leaves into the one stream buffer inside save()."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.save.copy"))

"""Mean seconds a save spends in its ckpt.save.gather span: the
device-to-host copy of every leaf (np.asarray, one leaf after another)
inside save()."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.save.gather"))

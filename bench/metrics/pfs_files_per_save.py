"""Mean ``files`` attribute of a save's ckpt.flush span: the files the
flush creates on the PFS (data files, the manifest, the flush journal)."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.save", "saves"), "ckpt.flush", "files"))

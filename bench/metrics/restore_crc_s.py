"""Thread-seconds of CRC a restore spends on arrival: its
ckpt.restore.crc spans (one a rank blob, on the reader threads) summed,
over the restores."""
from bench.spans import mean, per_tree, trees


def read(rec):
    return mean(per_tree(trees(rec, "ckpt.restore", "restores"), "ckpt.restore.crc"))

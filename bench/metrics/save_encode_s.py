"""Mean SaveStats.encode_time over the window's saves: the device-to-host
gather, encode, CRC and L1 write inside save()."""


def read(rec):
    saves = rec.get("saves")
    if not saves:
        return None
    return sum(s["encode_s"] for s in saves) / len(saves)

"""Megabytes per second delivered to the device: the bytes of the delivered
leaves, from their shapes, over window seconds.  A count, not a link
measurement."""


def read(sample):
    if "bytes_delivered" not in sample:
        return None
    return sample["bytes_delivered"] / sample["window_s"] / 1e6

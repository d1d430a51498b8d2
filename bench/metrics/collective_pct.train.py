"""Collective time of the traced window: the union of the device's
all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all ops (synchronous and async, ``trace.reduce``) as a share of
the window, averaged over the chips."""


def read(record):
    tr = record.get("trace")
    if record.get("kind") != "train" or not tr:
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]

"""Exposed collective time of the traced window: the part of the
collectives' time in which no other op runs on the same chip
(``trace.reduce``), as a share of the window, averaged over the chips.
What overlap with compute would still have to hide."""


def read(record):
    tr = record.get("trace")
    if record.get("kind") != "train" or not tr:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]

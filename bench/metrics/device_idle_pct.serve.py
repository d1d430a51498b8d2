"""Share of the traced window in which no operation ran on the device,
under the serving engine's host loop, averaged over the chips."""


def read(record):
    tr = record.get("trace")
    if record.get("kind") != "serve" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

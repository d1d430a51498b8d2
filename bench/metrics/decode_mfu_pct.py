"""The decode step's share of its roofline, over the traced rounds.

For each traced decode round the least time is the larger of its FLOPs
over the bf16 peak and its bytes over the HBM bandwidth (bench/flops:
every bf16 weight once, plus the live keys and values of the active
lanes, not the pages reserved).  The sum of those least times is divided
by the decode program's device time in the trace.  Decode is bound by
bytes, so this is a share of the bandwidth roofline."""


def read(record):
    if record.get("kind") != "serve" or not record.get("decode_device_s"):
        return None
    return 100.0 * record["decode_least_s"] / record["decode_device_s"]

"""The planner's error on its own plan: |predicted - measured| / measured
tokens per second, in percent.  Predicted is the searched plan's
``est_throughput`` (samples/s) times the sequence length; measured is the
window's tokens per second on the host clock."""


def read(record):
    if record.get("kind") != "train":
        return None
    meas = record["tok_per_s"]
    return 100.0 * abs(record["plan_est_tok_per_s"] - meas) / meas

"""The whole training step's share of the chips' peak: model FLOPs per
token (bench/flops, no recomputation counted) times tokens per second of
the window, over chips times the bf16 peak (bench/peaks.json)."""


def read(record):
    if record.get("kind") != "train":
        return None
    return (100.0 * record["flops_per_token"] * record["tok_per_s"]
            / record["peak_flops_per_s"])

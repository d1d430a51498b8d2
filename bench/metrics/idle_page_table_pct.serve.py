"""Share of the traced window in which the device is idle while the
serving engine's host is in page-table work: the innermost program span
around the idle time is ``serve.page_table`` (every ``PageManager`` call
from the engine, as eager device ops, and the host read of its result).
Read from the traced run's profile (bench/harness/program_spans.py)."""
from bench.harness import program_spans


def read(record):
    return program_spans.read(record, "page_table")

"""Share of the traced window in which the device is idle while the
serving engine's host loop is in its own work: the innermost program
span around the idle time is ``serve.round``, ``serve.admit``,
``serve.prefill``, ``serve.decode`` or ``serve.bookkeep`` (admission,
prompt blocks and dispatch, token and finish bookkeeping).  Read from
the traced run's profile (bench/harness/program_spans.py)."""
from bench.harness import program_spans


def read(record):
    return program_spans.read(record, "engine_loop")

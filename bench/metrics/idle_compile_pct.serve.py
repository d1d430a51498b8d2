"""Share of the traced window in which the device is idle while JAX
compiles: the innermost host event around the idle time is
``backend_compile_and_load``.  A warm engine compiles nothing, so this
reads 0 unless a call shape missed the jit caches.  Read from the traced
run's profile (bench/harness/program_spans.py)."""
from bench.harness import program_spans


def read(record):
    return program_spans.read(record, "compile")

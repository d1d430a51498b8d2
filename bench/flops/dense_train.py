"""Operation counts of a dense GQA decoder in a training cell: those of
``dense.py``.  Configurations of ``arch_type`` ``dense_train`` are dense
decoders checked against ``bench/reference/dense_train.py``."""
from __future__ import annotations

from bench.flops.dense import train_flops_per_token  # noqa: F401

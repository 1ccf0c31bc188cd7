"""`host_prep_ms` in a cell that keeps every worker busy."""

from . import host_prep_ms

LAYER, UNIT, MOVES = "host preparation", "ms", "proofs_per_s"
read = host_prep_ms.read

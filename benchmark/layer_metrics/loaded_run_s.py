"""`job_run_s` in a cell that keeps every worker busy: beside the one-client
cell's it says what sharing the interpreter lock and the chip costs a job."""

from . import job_run_s

LAYER, UNIT, MOVES = "queue and workers", "s", "proofs_per_s"
read = job_run_s.read

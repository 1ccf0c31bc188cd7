"""The plain reference the benchmark decides `correct` with: a pure-Python
BN254 pairing check, copied from the program (see each file's first lines)
and imported by nothing in `distributed_groth16_tpu/`."""

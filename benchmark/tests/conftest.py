"""Run by hand: `python -m pytest benchmark/tests -q` from the checkout's
root. Not part of the repo's tier-1 suite (`tests/`)."""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

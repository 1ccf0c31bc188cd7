"""Per-layer metrics, one small reader a file, found by the metric's name in
BENCHMARK.json's `per_layer`. A reader has

    LAYER, UNIT, MOVES    as its BENCHMARK.json entry says (a test holds
                          the two together)
    read(run) -> number   from the run's records, counters and reduced
                          trace (`run.py` builds `run`); None where there
                          is nothing to read, and the metric is then left
                          out of the line

A later PR adds a reader and an entry; it edits none that is there."""

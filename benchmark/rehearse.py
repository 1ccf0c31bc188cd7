#!/usr/bin/env python3
"""Rehearse the benchmark here, without the chip, before chip time is spent.

    python benchmark/rehearse.py [--workload NAME ...] [--extra FILE]
                                 [--seconds S] [--length N]

Runs each cell of BENCHMARK.json end to end on the CPU backend: the same
`run.py` pieces, the same data files, the same child load generator and
HTTP routes, once with `--trace 0` and once with `--trace 1`, with the
cell's configuration swapped for a tiny chain circuit (`circuits/
mult_chain.py`, `--length` constraints) so that a run takes a minute and
not an hour. What it proves is paths, arguments and data files. It prints
the shape of each result line and NO metric value: a number from a CPU
run is never a device number. `run.py` itself has no such switch.

`--extra FILE` adds the `configs`, `workloads`, `end_to_end` and
`per_layer` entries of a JSON file to BENCHMARK.json's for this rehearsal
only, and its `metric_workloads` to the lists of the metrics that are
there: how a later PR shows that its new cell needs new files and entries
and no edit (README.md, "A fifth cell").

The program's Pallas call sites run as their plain-XLA bodies on the CPU
(`ops/limb_kernels.use_pallas`); the kernels themselves under interpret
mode are `tests/test_pallas_interpret.py`'s business.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # a rehearsal never looks for a chip

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as bench_run  # noqa: E402
from benchmark.artefacts import CACHE_ROOT  # noqa: E402


def tiny(config: dict, length: int) -> dict:
    """The configuration with its circuit swapped; job kind, parties,
    workers and pool stay the cell's own."""
    out = dict(config)
    out["circuit"] = dict(
        config["circuit"], generator="mult_chain", params={"length": length}
    )
    out.pop("expect", None)
    return out


def shape(line: dict) -> dict:
    return {
        "rehearsal": True,
        "correct": line["correct"],
        "attempted": line["attempted"],
        "failed": line["failed"],
        "metrics": sorted(line["metrics"]),
        "device": {"platform": line["device"]["platform"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell's name; repeat it; default every cell")
    ap.add_argument("--extra", help="JSON file with further entries of "
                                    "BENCHMARK.json's lists")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--length", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    bench = bench_run.load_bench()
    if args.extra:
        with open(args.extra) as f:
            extra = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] += extra.get(key, [])
        for metric in bench["end_to_end"] + bench["per_layer"]:
            for name, cells in extra.get("metric_workloads", {}).items():
                if metric["name"] == name and "workloads" in metric:
                    metric["workloads"] += cells
    names = args.workload or [w["name"] for w in bench["workloads"]]
    bad = 0
    for name in names:
        cell, config, traffic = bench_run.load_cell(bench, name)
        for trace in (False, True):
            line = bench_run.run_cell(
                cell, tiny(config, args.length), traffic, bench,
                seed=args.seed, seconds=args.seconds, trace=trace,
                jax=jax, devices=jax.devices()[:cell["chips"]],
                on_chip=False,
                cache_root=os.path.join(CACHE_ROOT, "rehearsal"),
            )
            print(f"rehearsed {name} trace={int(trace)} "
                  + json.dumps(shape(line)), flush=True)
            bad += not line["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""From a profiler trace (`.xplane.pb`) to numbers, with
`jax.profiler.ProfileData` and nothing else.

What a TPU v5e trace holds (looked at by hand, PR 22, jax 0.9.0): one
plane per chip, `/device:TPU:<n>`, whose line `XLA Ops` has one event per
operation that ran on the chip (named by its whole HLO text; 370,000 of
them in 8 s of single-node proving) and whose line `XLA Modules` has one
event per launch of a jitted program, named `jit_<function>(<fingerprint>)`
(a third line, `Async XLA Ops`, holds copies in flight and is not counted
as busy); and a plane `/host:CPU` with one line per host thread, holding
the runtime's own events (`PJRT_LoadedExecutable_Execute`, ...), with the
Python tracer on its calls (`$file:line fn`), and, while a capture runs,
one event per `tracing.span` of the program (`job`, `load`, `witness`,
`packing`, `prove.h`, `dmsm`, ...) because every span then also enters a
`jax.profiler.TraceAnnotation`. All planes share one clock.

The reduction:

  busy_s     per chip, the union of the intervals of `XLA Ops` (of
             `XLA Modules` where a plane has no op line), averaged over
             the chips the cell uses
  window_s   the traced slice: first event start to last event end
  per job    totals between the first and the last `job` annotation
             boundary inside the slice, divided by the `job` annotations
             that lie there (a job the slice cuts has no annotation: the
             event is written when the span ends). In a one-client cell
             jobs never overlap, so these are whole jobs and the launch
             count is exact
  groups     launches' time by program name: a group is a data file
             `kernel_groups/<group>.json` with the substrings that name
             its programs
  gaps       the idle intervals of the chip inside the job interval, each
             named by the innermost program span that covers most of it
             (`no_span` if none does); span names are data too
             (`host_spans/*.txt`)
"""

from __future__ import annotations

import fnmatch
import glob
import json
import os
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))

DEVICE_PLANE_PREFIX = "/device:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_groups() -> dict[str, list[str]]:
    """{group: [substring of a program's name, ...]}, one file a group."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "kernel_groups", "*.json"))):
        with open(path) as f:
            doc = json.load(f)
        out[os.path.splitext(os.path.basename(path))[0]] = list(doc["programs"])
    return out


def load_span_patterns() -> list[str]:
    """fnmatch patterns of the program's span names, every file of
    `host_spans/` together."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "host_spans", "*.txt"))):
        with open(path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if line:
                    out.append(line)
    return out


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """`jit__msm_tree_jit(1234)` -> `_msm_tree_jit`."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


@dataclass
class RawTrace:
    """Seconds on the trace's clock."""

    ops: dict[str, list[tuple[float, float]]] = field(default_factory=dict)
    # per device plane: (start, end, program name) of every launch
    launches: dict[str, list[tuple[float, float, str]]] = field(
        default_factory=dict
    )
    # (start, end, name) of the program's spans on any host thread
    spans: list[tuple[float, float, str]] = field(default_factory=list)
    first: float = float("inf")
    last: float = float("-inf")


def read_xplane(path: str, span_patterns: list[str]) -> RawTrace:
    from jax.profiler import ProfileData

    raw = RawTrace()
    exact = {p for p in span_patterns if not any(c in p for c in "*?[")}
    globs = [p for p in span_patterns if p not in exact]
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    sink = raw.ops.setdefault(name, [])
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        sink.append((s, e))
                elif line.name == MODULES_LINE:
                    sink = raw.launches.setdefault(name, [])
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        sink.append((s, e, program_name(ev.name)))
        elif name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if s < raw.first:
                        raw.first = s
                    if e > raw.last:
                        raw.last = e
                    n = ev.name
                    if n[:1] == "$":
                        continue  # the Python tracer's calls
                    if n in exact or any(fnmatch.fnmatchcase(n, g) for g in globs):
                        raw.spans.append((s, e, n))
    for plane, evs in list(raw.ops.items()) + list(raw.launches.items()):
        if evs:
            raw.first = min(raw.first, min(ev[0] for ev in evs))
            raw.last = max(raw.last, max(ev[1] for ev in evs))
    return raw


def name_gap(gap: tuple[float, float], spans) -> str:
    """The innermost (shortest) span that covers more than half the gap."""
    gs, ge = gap
    best, best_len = "no_span", float("inf")
    for s, e, n in spans:
        if e <= gs or s >= ge:
            continue
        if min(e, ge) - max(s, gs) > 0.5 * (ge - gs) and e - s < best_len:
            best, best_len = n, e - s
    return best


def reduce_trace(path: str, chips: int, groups: dict | None = None,
                 span_patterns: list[str] | None = None) -> dict:
    """Everything the per-layer readers and the last line take from one
    trace. Times in seconds; `per_job` is None when the slice holds no
    whole job."""
    groups = load_groups() if groups is None else groups
    patterns = load_span_patterns() if span_patterns is None else span_patterns
    raw = read_xplane(path, patterns)
    planes = sorted(set(raw.ops) | set(raw.launches))
    if not planes:
        raise ValueError(f"{path}: no device plane: nothing ran on a chip")

    def busy_intervals(plane: str):
        evs = raw.ops.get(plane) or [(s, e) for s, e, _ in raw.launches[plane]]
        return merge(evs)

    busy = {p: busy_intervals(p) for p in planes}
    out = {
        "window_s": raw.last - raw.first,
        "busy_s": sum(union_seconds(b) for b in busy.values()) / chips,
        "device_planes": planes,
        "launches": sum(len(v) for v in raw.launches.values()),
        "span_events": len(raw.spans),
        "per_job": None,
    }

    by_program: dict[str, float] = {}
    for evs in raw.launches.values():
        for s, e, n in evs:
            by_program[n] = by_program.get(n, 0.0) + (e - s)
    top = sorted(by_program.items(), key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [[n, t] for n, t in top]

    jobs = [(s, e) for s, e, n in raw.spans if n == "job"]
    if jobs:
        lo = min(s for s, _ in jobs)
        hi = max(e for _, e in jobs)
        n_jobs = len(jobs)
        launches = [
            ev for evs in raw.launches.values() for ev in evs
            if lo <= ev[0] < hi
        ]
        group_s = {}
        for g, needles in groups.items():
            group_s[g] = sum(
                min(e, hi) - s for s, e, n in launches
                if any(x in n for x in needles)
            ) / n_jobs
        out["per_job"] = {
            "jobs": n_jobs,
            "interval_s": hi - lo,
            "busy_s": sum(
                union_seconds(clip(b, lo, hi)) for b in busy.values()
            ) / chips / n_jobs,
            "launches": len(launches) / n_jobs,
            "group_s": group_s,
        }
    else:
        lo, hi = raw.first, raw.last
    # idle gaps of the first chip's plane inside the job interval (inside
    # the slice where it holds no whole job)
    edges = [lo] + [t for iv in clip(busy[planes[0]], lo, hi) for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    # naming a gap walks every span, so only gaps that can matter get a
    # name: the 2000 longest hold nearly all the idle time
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(name_gap(g, raw.spans), g[1] - g[0]) for g in gaps[:2000]]
    totals: dict[str, float] = {}
    for n, t in named:
        totals[n] = totals.get(n, 0.0) + t
    out["idle_gaps"] = [[n, t] for n, t in named[:5]] + [
        [f"all:{n}", t]
        for n, t in sorted(totals.items(), key=lambda kv: -kv[1])[:5]
    ]
    return out


def summarize(path: str, names_per_line: int = 12) -> dict:
    """Planes, lines, event counts and the first distinct names of each
    line: what to look at by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            n, names, first, last = 0, {}, None, None
            for ev in line.events:
                n += 1
                if first is None:
                    first = ev.start_ns
                last = ev.start_ns + ev.duration_ns
                if len(names) < names_per_line and ev.name[:1] != "$":
                    names.setdefault(ev.name, 0)
                if ev.name in names:
                    names[ev.name] += 1
            lines[line.name] = {
                "events": n, "first_ns": first, "last_ns": last,
                "names": names,
            }
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import sys

    print(json.dumps(summarize(sys.argv[1]), indent=1))

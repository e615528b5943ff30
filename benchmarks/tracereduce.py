"""From a profiler capture to the traced run's device numbers.

After ``dpf_tpu/utils/profiling.summarize_trace`` (self time per op on
the device's "XLA Ops" tracks), extended with the union of busy
intervals (ops and whole program runs), the idle share of the traced
window, and the longest idle gaps, each named by the benchmark's host
annotation open over it.

``load`` keeps only what the reduction reads, in a compact form that
``save`` writes and ``load_saved`` reads back (the recorded fixture):

    {"device": {plane: {"ops": [[op, start_ns, dur_ns], ...],
                        "modules": [[program, start_ns, dur_ns], ...]}},
     "host": [[annotation, start_ns, dur_ns], ...]}

``ops`` are the "XLA Ops" track, named by their HLO instruction;
``modules`` the "XLA Modules" track, one event per program run.
``host`` holds the ``bench.*`` annotations; ``bench.window`` spans the
measured window.
"""

from __future__ import annotations

import glob
import gzip
import json
import os

TOP = 10
WINDOW = "bench.window"
NO_SPAN = "(no bench span)"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    device, host = {}, []
    tracks = {"XLA Ops": "ops", "XLA Modules": "modules"}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in tracks:
                    d = device.setdefault(plane.name,
                                          {"ops": [], "modules": []})
                    d[tracks[line.name]].extend(
                        [e.name.split(" = ")[0], e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": device, "host": host}


def save(compact: dict, path: str) -> None:
    with gzip.open(path, "wt", compresslevel=6) as f:
        json.dump(compact, f)


def load_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events) -> dict:
    """Self time (ns) per op name on one track: a nested op's time is
    taken off its parent."""
    stack, by_op = [], {}
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            by_op[parent] -= d
        by_op[name] = by_op.get(name, 0.0) + d
        stack.append((s + d, name))
    return by_op


def reduce(compact: dict) -> dict:
    """What the traced window holds, per chip then averaged over the
    chips with device activity in it:

    * ``busy_s``: the union of the intervals in which an op or a program
      ran (a program's run covers the short gaps between its ops, and
      stays whole where a long loop's op events outrun the profiler);
    * ``ops_s`` and ``program_s``: the op and program unions alone;
    * ``window_s``, ``idle_share``;
    * ``top_ops``: self time per op, and ``top_gaps``: the idle gaps,
      each named by the benchmark annotation open over it ([name,
      seconds], longest first)."""
    wins = [h for h in compact["host"] if h[0] == WINDOW]
    if len(wins) != 1:
        raise ValueError("expected one %s annotation, found %d"
                         % (WINDOW, len(wins)))
    t0, t1 = wins[0][1], wins[0][1] + wins[0][2]

    def clip(events):
        return [[n, max(s, t0), min(s + d, t1) - max(s, t0)]
                for n, s, d in events if s < t1 and s + d > t0]

    def length(merged):
        return sum(e - s for s, e in merged)

    busy, ops_s, prog_s, ops, first = [], [], [], {}, None
    for plane in sorted(compact["device"]):
        ops_in = clip(compact["device"][plane]["ops"])
        prog_in = clip(compact["device"][plane]["modules"])
        if not ops_in and not prog_in:
            continue
        merged = union([s, s + d] for _, s, d in ops_in + prog_in)
        busy.append(length(merged))
        ops_s.append(length(union([s, s + d] for _, s, d in ops_in)))
        prog_s.append(length(union([s, s + d] for _, s, d in prog_in)))
        for name, ns in self_times(ops_in).items():
            ops[name] = ops.get(name, 0.0) + ns
        if first is None:
            first = merged
    if not busy:
        raise ValueError("no device op ran inside the traced window")
    spans = [h for h in compact["host"] if h[0] != WINDOW]
    gaps, prev = [], t0
    for s, e in first + [[t1, t1]]:
        if s > prev:
            gaps.append([_name_gap(spans, prev, s), (s - prev) / 1e9])
        prev = max(prev, e)

    def mean_s(xs):
        return sum(xs) / len(xs) / 1e9

    window_s = (t1 - t0) / 1e9
    top_ops = sorted(([n, v / 1e9] for n, v in ops.items()),
                     key=lambda x: -x[1])[:TOP]
    return {"busy_s": mean_s(busy), "window_s": window_s,
            "idle_share": 1.0 - mean_s(busy) / window_s,
            "ops_s": mean_s(ops_s), "program_s": mean_s(prog_s),
            "top_ops": top_ops,
            "top_gaps": sorted(gaps, key=lambda x: -x[1])[:TOP]}


def _name_gap(spans, s: float, e: float) -> str:
    """The innermost benchmark annotation open at the gap's midpoint."""
    mid = (s + e) / 2
    open_ = [h for h in spans if h[1] <= mid < h[1] + h[2]]
    return min(open_, key=lambda h: h[2])[0] if open_ else NO_SPAN

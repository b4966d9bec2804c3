"""Reduction of a profiler trace to device busy time, idle gaps and ops.

The trace is the `.xplane.pb` that `jax.profiler` writes. Device planes are
named ``/device:TPU:<id>``; the operations a device ran are the events of
its ``XLA Ops`` line. Host spans (the harness's `TraceAnnotation`s and the
Python calls the profiler records under them) are the events of the host
plane's line that holds the window's start marker. All events carry start
and duration in nanoseconds on one clock.

A TPU keeps its op events in buffers of fixed size. A while loop of small
ops fills them within seconds; the device then marks the rest of the trace
with a "Trace Buffers Dropped" event, after which it records nothing. The
window runs from a start marker to an end marker on the host, and ends
earlier where the first device dropped its buffers; everything below is
measured inside it:

- busy: the union of a device's op intervals inside the window;
- idle gaps: the stretches of the window that the union leaves uncovered,
  each charged to the innermost host span that covers its middle, and
  summed by span name;
- ops: self time per op name (its duration less that of the ops nested in
  it), summed inside the window;
- the loop: inside a while loop each op of the body runs once per trip,
  so the loop's trips in the window are the number of runs shared by the
  ops that hold the most runs between them (`loop_trips`), and the loop's
  part of the window runs from the first of those runs to its end. Its busy time over its trips is
  the device's time per trip, whatever the host did before the loop.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DROPPED = "Trace Buffers Dropped"


@dataclasses.dataclass
class Loop:
    """One device's part of the window from its loop's first trip."""

    pre_busy_s: float  # busy seconds before the loop's first trip
    busy_s: float  # busy seconds from the loop's first trip to the window's end
    trips: int  # while-loop trips in that part


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the traced window
    busy_s: dict  # device id -> seconds in which an op ran
    idle_gaps: list  # [[host span name, idle seconds summed over devices]]
    device_ops: list  # [[op name, self seconds summed over devices]]
    loops: dict = dataclasses.field(default_factory=dict)  # device id -> Loop


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_line(line, lo: float, hi: float):
    """(intervals [n, 2] ns, names) of the events that overlap [lo, hi],
    sorted by start and, at one start, longest first."""
    iv, names = [], []
    for e in line.events:
        s = e.start_ns
        if s < hi and s + e.duration_ns > lo:
            iv.append((s, s + e.duration_ns))
            names.append(e.name)
    iv = np.array(iv, np.float64).reshape(-1, 2)
    order = np.lexsort((-iv[:, 1], iv[:, 0]))
    return iv[order], [names[i] for i in order]


def union(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Merged [m, 2] intervals of ``iv`` clipped to [lo, hi]."""
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.ones(len(iv), bool)
    first[1:] = iv[1:, 0] > reach[:-1]
    at = np.flatnonzero(first)
    return np.stack([iv[at, 0], np.maximum.reduceat(iv[:, 1], at)], axis=1)


def gaps(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The [g, 2] stretches of [lo, hi] that ``merged`` leaves uncovered."""
    edges = np.concatenate([[lo], merged.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def attribute(spans: list, gap: np.ndarray) -> dict:
    """Span name -> idle seconds: each gap goes to the shortest host span
    (name, start_ns, end_ns) that covers its middle, or to "none"."""
    mid = (gap[:, 0] + gap[:, 1]) / 2
    order = np.argsort(mid)
    mid, length = mid[order], (gap[:, 1] - gap[:, 0])[order]
    label = np.zeros(len(mid), np.int64)  # 0: no span
    names = ["none"]
    for name, s, e in sorted(spans, key=lambda x: x[1] - x[2]):  # longest first
        i = np.searchsorted(mid, s, side="left")
        j = np.searchsorted(mid, e, side="right")
        if j > i:
            label[i:j] = len(names)
            names.append(name)
    sums = np.bincount(label, weights=length, minlength=len(names))
    out: dict = {}
    for k in np.flatnonzero(np.bincount(label, minlength=len(names))):
        out[names[k]] = out.get(names[k], 0.0) + float(sums[k]) / 1e9
    return out


def self_times(iv: np.ndarray, names: list) -> dict:
    """Op name -> self nanoseconds of events sorted as `read_line` sorts
    them (an event's duration less that of the events nested in it)."""
    own = iv[:, 1] - iv[:, 0]
    if len(iv) > 1 and not np.all(iv[1:, 0] >= iv[:-1, 1]):
        own = own.copy()
        stack: list = []  # indices of the events that hold the current one
        for i, (s, e) in enumerate(iv):
            while stack and iv[stack[-1], 1] <= s:
                stack.pop()
            if stack:
                own[stack[-1]] -= e - s
            stack.append(i)
    ids: dict = {}
    idx = np.fromiter((ids.setdefault(n, len(ids)) for n in names), np.int64, len(names))
    sums = np.bincount(idx, weights=own, minlength=len(ids))
    return {n: float(sums[i]) for n, i in ids.items()}


def loop_trips(runs: dict) -> int:
    """Trips of a while loop from ``runs`` (op name -> times it ran): group
    the ops by how often each ran; the trips are that number for the group
    that holds the most runs in all (the larger number, where two tie).
    Each op of the body runs once per trip, so over more than a few trips
    the body's group outweighs the code before and after the loop and the
    branches taken on some trips only."""
    share = collections.Counter(runs.values())
    return max(share, key=lambda n: (share[n] * n, n)) if share else 0


def window_bounds(pd, start: str, end: str):
    """(start_ns, end_ns, host line) of the window between the first host
    events named ``start`` and ``end``; the line is the one holding
    ``start``."""
    host = pd.find_plane_with_name(HOST_PLANE)
    if host is None:
        raise ValueError(f"trace has no {HOST_PLANE} plane")
    found = {}
    for line in host.lines:
        for e in line.events:
            if e.name in (start, end) and e.name not in found:
                found[e.name] = (e.start_ns, line)
    if start not in found or end not in found:
        raise ValueError(f"trace lacks the host markers {start!r} and {end!r}")
    return found[start][0], found[end][0], found[start][1]


def device_lines(pd, prefix: str = DEVICE_PREFIX, line_name: str = OPS_LINE) -> dict:
    """Device id -> (op line, start ns of its first dropped-buffers mark or
    None), for every plane named ``prefix<id>``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(prefix):
            continue
        ops, dropped = None, None
        for line in plane.lines:
            if line.name == line_name:
                ops = line
                continue
            for e in line.events:
                if e.name == DROPPED and (dropped is None or e.start_ns < dropped):
                    dropped = e.start_ns
        if ops is not None:
            out[int(plane.name[len(prefix):])] = (ops, dropped)
    return out


def reduce(pd, start: str, end: str, lines: dict, top: int = 10) -> Reduced:
    """Busy time per device, idle time by host span, op self times and the
    loop between the host markers ``start`` and ``end``, cut where the
    first of the devices in ``lines`` (device id -> (op line, dropped ns or
    None)) dropped its trace buffers."""
    lo, hi, host_line = window_bounds(pd, start, end)
    for _, dropped in lines.values():
        if dropped is not None:
            hi = min(hi, dropped)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in host_line.events]
    busy, idle, ops, loops = {}, {}, {}, {}
    for dev, (line, _) in sorted(lines.items()):
        iv, names = read_line(line, lo, hi)
        merged = union(iv, lo, hi)
        busy[dev] = float(np.sum(merged[:, 1] - merged[:, 0])) / 1e9
        runs = collections.Counter(names)
        trips = loop_trips(runs)
        if trips > 1:
            at = max(lo, min(s for (s, _), n in zip(iv, names) if runs[n] == trips))
            part = union(iv, at, hi)
            loop_s = float(np.sum(part[:, 1] - part[:, 0])) / 1e9
            loops[dev] = Loop(pre_busy_s=busy[dev] - loop_s, busy_s=loop_s, trips=trips)
        for name, sec in attribute(spans, gaps(merged, lo, hi)).items():
            idle[name] = idle.get(name, 0.0) + sec
        for name, ns in self_times(iv, names).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9

    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda x: -x[1])[:top]]

    return Reduced(
        window_s=float(hi - lo) / 1e9, busy_s=busy, idle_gaps=most(idle), device_ops=most(ops),
        loops=loops,
    )

"""Reduction of a profiler trace to device busy time, idle share inside
engine steps, the top device operations and the longest idle gaps.

The benchmark marks its measured window with a host span named
``bench_window`` and each engine step with ``bench_step``
(``jax.profiler.TraceAnnotation``), so both land on the trace's own clock.
Device time is the union of the operation events on each device plane's
"XLA Ops" line (every line of the plane where that line is missing), moved
onto the host clock by ``clock_offset``; an operation is named by its HLO
instruction name ("%fusion.28"). The partition of idle inside steps by
host phase (``phases.reduce``) is merged into the result.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench_window"
STEP = "bench_step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DONE = "tpu::System::Execute=>Done"

Interval = Tuple[int, int]


def load(path: str) -> List[Dict]:
    """An ``.xplane.pb`` as plain planes -> lines -> (name, start, dur) ns.
    Of a device plane that has an "XLA Ops" line only that line and "XLA
    Modules" are read: the reduction uses no other."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if is_device(plane.name) and any(ln.name == OPS_LINE for ln in lines):
            lines = [ln for ln in lines if ln.name in (OPS_LINE, MODULES_LINE)]
        planes.append({"name": plane.name, "lines": [
            {"name": ln.name,
             "events": [(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in ln.events]} for ln in lines]})
    return planes


def is_device(plane_name: str) -> bool:
    """An accelerator's plane ("/device:TPU:0"), not the host's or a custom
    one ("/device:CUSTOM:...")."""
    return re.fullmatch(r"/device:(?!CPU|CUSTOM)[A-Z]+:\d+",
                        plane_name) is not None


def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def length(iv: Sequence[Interval]) -> int:
    return sum(b - a for a, b in iv)


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _op_lines(plane: Dict) -> List[Dict]:
    ops = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    return ops or plane["lines"]


def busy(plane: Dict, shift: int, lo: int, hi: int) -> List[Interval]:
    """``union(clip(...))`` of the plane's operation intervals moved by
    ``shift`` onto the host clock, in numpy: a traced window holds millions
    of operations."""
    ev = [e for ln in _op_lines(plane) for e in ln["events"] if e[2] > 0]
    s = np.fromiter((e[1] for e in ev), np.int64, len(ev)) + shift
    e = s + np.fromiter((e[2] for e in ev), np.int64, len(ev))
    keep = (e > lo) & (s < hi)
    s, e = np.maximum(s[keep], lo), np.minimum(e[keep], hi)
    if not len(s):
        return []
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > run[:-1]])
    return list(zip(s[first].tolist(),
                    np.r_[run[first[1:] - 1], run[-1]].tolist()))


def clock_offset(plane: Dict, host: List[Tuple[str, int, int]],
                 reach: int = 20_000_000, bin_ns: int = 100_000) -> int:
    """Nanoseconds to add to the device plane's times to put them on the host
    clock (on a v5e they run about 1.8 ms behind). The host sees each
    program done a fixed latency after the device ends it, so among the
    differences (host "done" event - device program end) over every pair
    within ``reach`` of each other, the true offset is the most frequent
    one: the mode over ``bin_ns`` bins, refined to the median of the
    differences in that bin. 0 when the trace has no programs."""
    ends = sorted(s + d for ln in plane["lines"]
                  if ln["name"] == MODULES_LINE for _, s, d in ln["events"])
    done = sorted(s for n, s, _ in host if n == DONE)
    diffs = []
    for e in ends:
        lo = bisect.bisect_left(done, e - reach)
        hi = bisect.bisect_right(done, e + reach)
        diffs.extend(d - e for d in done[lo:hi])
    if not diffs:
        return 0
    counts: Dict[int, int] = defaultdict(int)
    for x in diffs:
        counts[x // bin_ns] += 1
    mode = max(counts, key=lambda b: (counts[b], -abs(b)))
    near = sorted(x for x in diffs if abs(x // bin_ns - mode) <= 1)
    return near[len(near) // 2]


def _host_at(t: int, lines: List[List], starts: List[List[int]],
             walk: int = 4096) -> str:
    """Name of the innermost host span open at ``t`` over all host threads
    (spans of one thread nest, so the latest-started one that is still open
    is that thread's innermost; the search looks ``walk`` spans back)."""
    best = None
    for ln, st in zip(lines, starts):
        i = bisect.bisect_right(st, t) - 1
        stop = max(-1, i - walk)
        while i > stop:
            n, s, d = ln[i]
            if s + d > t and n != WINDOW:
                if best is None or d < best[0]:
                    best = (d, n)
                break
            i -= 1
    return best[1] if best else "(no host span)"


def reduce(planes: List[Dict], top: int = 10,
           max_gaps: int = 500) -> Optional[Dict]:
    """Busy and idle numbers over the ``bench_window`` span, with the keys of
    ``phases.reduce`` merged in, or None when the trace holds no window or
    no device plane. The ``max_gaps`` longest idle gaps are named by the
    host span open at their middle; the rest are summed as "(shorter
    gaps)"."""
    import phases   # it builds on this module's interval arithmetic
    host_lines = [sorted(ln["events"], key=lambda e: e[1])
                  for p in planes if not is_device(p["name"])
                  for ln in p["lines"]]
    host = [e for ln in host_lines for e in ln]
    win = [(s, s + d) for n, s, d in host if n == WINDOW]
    devices = [p for p in planes if is_device(p["name"])]
    if not win or not devices:
        return None
    w0, w1 = win[0]
    steps = union(clip([(s, s + d) for n, s, d in host if n == STEP], w0, w1))
    busy_ns, in_steps_ns, per_op = [], [], defaultdict(int)
    busy_all: List[Interval] = []
    offsets = []
    for p in devices:
        off = clock_offset(p, host)
        offsets.append(off)
        b = busy(p, off, w0, w1)
        busy_ns.append(length(b))
        in_steps_ns.append(length(intersect(b, steps)))
        busy_all = union(busy_all + b)
        by_name: Dict[str, int] = defaultdict(int)
        for ln in _op_lines(p):
            for n, s, d in ln["events"]:
                if d > 0 and s + off >= w0 and s + off + d <= w1:
                    by_name[n] += d
        for n, d in by_name.items():
            per_op[n.split(" = ")[0]] += d
    n_dev = len(devices)
    step_ns = length(steps)
    idle: Dict[str, int] = defaultdict(int)
    starts = [[e[1] for e in ln] for ln in host_lines]
    idle_iv = sorted(gaps(busy_all, w0, w1), key=lambda g: g[0] - g[1])
    for a, b in idle_iv[:max_gaps]:
        idle[_host_at((a + b) // 2, host_lines, starts)] += b - a
    rest = length(idle_iv[max_gaps:])
    if rest:
        idle["(shorter gaps)"] += rest
    ns = 1e-9
    return {
        **phases.reduce(planes),
        "window_s": (w1 - w0) * ns,
        "clock_offset_s": [o * ns for o in offsets],
        "busy_s": sum(busy_ns) / n_dev * ns,
        "step_s": step_ns * ns,
        "busy_in_steps_s": sum(in_steps_ns) / n_dev * ns,
        "device_ops": [[n, d * ns / n_dev] for n, d in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, d * ns] for n, d in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }

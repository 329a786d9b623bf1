"""Partition of the device's idle time inside engine steps by what the host
was doing.

The engine opens a profiler span ``engine.step`` around each step that has
work, and one leaf span ``engine.<phase>`` per host phase inside it
(``repro.serving.tracing.Phase``: form_batch, cache_match, kv_gather,
dispatch, device_wait, kv_insert, score, record). Idle inside steps is
counted exactly as ``trace_reduce`` counts it for
``device_idle_in_steps.offline``: the ``bench_step`` spans in the
``bench_window`` minus the union of the device's operations, moved onto the
host clock by ``trace_reduce.clock_offset``. Each idle interval is split
into disjoint classes, by interval intersection over every gap:

``idle_in_program_s``    a program ("XLA Modules") runs on the device but
                         no operation does
``idle_in_phase_s``      no program runs, by the innermost (shortest) open
                         ``engine.*`` leaf span on the host
``idle_outside_phases_s``  the rest

The three sum to idle in steps. Device times are the mean over device
planes, as ``trace_reduce.reduce`` gives ``busy_in_steps_s``, which merges
this module's keys into its result; ``shares`` turns them into the
per-layer metrics' percentages.

Reduce a saved trace:

    python3 benchmarks/chip/phases.py <trace.xplane.pb>
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as T

PREFIX = "engine."
STEP = "engine.step"

Labelled = Tuple[int, int, str]


def innermost(spans: Sequence[Labelled]) -> List[Labelled]:
    """Disjoint labelled intervals: each stretch covered by some span takes
    the label of the shortest span open there (ties by name)."""
    pts = sorted({p for a, b, _ in spans for p in (a, b)})
    todo = sorted(spans)
    out: List[Labelled] = []
    active: List[Labelled] = []
    i = 0
    for p, q in zip(pts, pts[1:]):
        while i < len(todo) and todo[i][0] <= p:
            active.append(todo[i])
            i += 1
        active = [s for s in active if s[1] > p]
        if not active:
            continue
        name = min(active, key=lambda s: (s[1] - s[0], s[2]))[2]
        if out and out[-1][2] == name and out[-1][1] == p:
            out[-1] = (out[-1][0], q, name)
        else:
            out.append((p, q, name))
    return out


def by_label(iv: Sequence[T.Interval],
             labelled: Sequence[Labelled]) -> Dict[str, int]:
    """Nanoseconds of the sorted disjoint ``iv`` under each label."""
    out: Dict[str, int] = defaultdict(int)
    i = j = 0
    while i < len(iv) and j < len(labelled):
        a, b = max(iv[i][0], labelled[j][0]), min(iv[i][1], labelled[j][1])
        if a < b:
            out[labelled[j][2]] += b - a
        if iv[i][1] < labelled[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(planes: List[Dict]) -> Optional[Dict]:
    """The partition of idle inside steps, in seconds, with
    ``programs_in_window`` (programs that start in the window, mean over
    devices) and ``engine_steps`` (``engine.step`` spans that start in it);
    None when the trace holds no window or no device plane."""
    host = [e for p in planes if not T.is_device(p["name"])
            for ln in p["lines"] for e in ln["events"]]
    win = [(s, s + d) for n, s, d in host if n == T.WINDOW]
    devices = [p for p in planes if T.is_device(p["name"])]
    if not win or not devices:
        return None
    w0, w1 = win[0]
    steps = T.union(T.clip([(s, s + d) for n, s, d in host if n == T.STEP],
                           w0, w1))
    leaves = innermost([(a, b, n[len(PREFIX):]) for n, s, d in host
                        if n.startswith(PREFIX) and n != STEP
                        for a, b in T.clip([(s, s + d)], w0, w1)])
    in_program = outside = programs = 0
    phase: Dict[str, int] = defaultdict(int)
    for p in devices:
        off = T.clock_offset(p, host)
        busy = T.busy(p, off, w0, w1)
        mods = [(s + off, s + off + d) for ln in p["lines"]
                if ln["name"] == T.MODULES_LINE for _, s, d in ln["events"]]
        programs += sum(1 for a, _ in mods if w0 <= a < w1)
        progs = T.union(T.clip([m for m in mods if m[1] > m[0]], w0, w1))
        idle = T.intersect(steps, T.gaps(busy, w0, w1))
        rest = T.intersect(idle, T.gaps(progs, w0, w1))
        in_program += T.length(idle) - T.length(rest)
        named = by_label(rest, leaves)
        for k, v in named.items():
            phase[k] += v
        outside += T.length(rest) - sum(named.values())
    s = 1e-9 / len(devices)
    return {
        "idle_in_program_s": in_program * s,
        "idle_in_phase_s": {k: v * s for k, v in sorted(phase.items())},
        "idle_outside_phases_s": outside * s,
        "programs_in_window": programs / len(devices),
        "engine_steps": sum(1 for n, a, _ in host
                            if n == STEP and w0 <= a < w1),
    }


def shares(red: Dict) -> Dict[str, float]:
    """Each class of idle in steps as a percentage of step time, from a
    ``trace_reduce.reduce`` result with this module's keys merged in: the
    shares sum to ``device_idle_in_steps.offline``."""
    step = red["step_s"]
    out = {"idle_in_program": 100.0 * red["idle_in_program_s"] / step}
    for k, v in red["idle_in_phase_s"].items():
        out[f"idle_in_{k}"] = 100.0 * v / step
    out["idle_outside_phases"] = 100.0 * red["idle_outside_phases_s"] / step
    if red["engine_steps"]:
        out["programs_per_step"] = (red["programs_in_window"]
                                    / red["engine_steps"])
    return out


def main(argv: Sequence[str]) -> int:
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    planes = T.load(argv[0])
    red = T.reduce(planes)
    if red is None:
        print("no bench_window span or no device plane", file=sys.stderr)
        return 1
    if red["step_s"]:
        red["shares"] = shares(red)
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

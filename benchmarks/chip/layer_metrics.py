"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader gets the run's context: ``requests`` (the window's scored
requests: ``n_input``, ``n_cached``, step ``path``, ``sent``, ``done``),
``batches`` (the engine's ``BatchRecord`` of each step that ended in the
window), ``trace`` (the reduced profiler trace, or None), ``compiles``
(backend compiles that ended in the window, and their seconds), ``peak``
(the chip's peaks, or None off the chip) and ``request_flops``.
"""
from __future__ import annotations

from typing import Optional

import phases


def hit_share(ctx) -> Optional[float]:
    n = sum(r["n_input"] for r in ctx.requests)
    if not n:
        return None
    return 100.0 * sum(r["n_cached"] for r in ctx.requests) / n


def padding_waste(ctx) -> Optional[float]:
    paid = sum(b.padded_tokens for b in ctx.batches)
    if not paid:
        return None
    return 100.0 * (1.0 - sum(b.computed_tokens for b in ctx.batches) / paid)


def step_ms(ctx, path: str) -> Optional[float]:
    walls = [b.wall for b in ctx.batches if b.jit_path == path]
    return 1000.0 * sum(walls) / len(walls) if walls else None


def step_mfu(ctx) -> Optional[float]:
    """FLOPs the window's scored work needs / (summed step wall x peak)."""
    if ctx.peak is None:
        return None
    by_rid = {r["sent"].result["req_id"]: r for r in ctx.requests}
    need = wall = 0.0
    for b in ctx.batches:
        rows = [by_rid[i] for i in b.req_ids if i in by_rid]
        if not rows:
            continue
        wall += b.wall
        need += sum(ctx.request_flops(r["n_input"] - r["n_cached"],
                                      r["n_cached"]) for r in rows)
    if not wall:
        return None
    return 100.0 * need / (wall * ctx.peak["bf16_flops"])


def idle_in_steps(ctx) -> Optional[float]:
    t = ctx.trace
    if t is None or not t["step_s"]:
        return None
    return 100.0 * (1.0 - t["busy_in_steps_s"] / t["step_s"])


def compiles(ctx) -> float:
    return float(ctx.compiles[0])


def phase_share(ctx, key: str) -> Optional[float]:
    """``phases.shares`` of the reduced trace: a class of idle in steps, %
    of step time, or ``programs_per_step``. A phase that ran with no idle
    under it reads 0; nothing is read from a trace without engine steps."""
    t = ctx.trace
    if t is None or not t["step_s"] or not t["engine_steps"]:
        return None
    return phases.shares(t).get(key, 0.0)

"""Arithmetic shared by the metric readers in ``metrics/``."""
from __future__ import annotations

import numpy as np

from . import counts as C


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def tokens_in_window(run) -> int:
    return sum(1 for s in run.served + run.carried for t in s.token_times
               if 0.0 <= t <= run.window_s)


def ttft_s(run) -> list:
    """Due to first token for every request due in the window; one with no
    first token by the window's end counts at the end."""
    out = []
    for s in run.served:
        first = s.token_times[0] if s.token_times else run.window_s
        out.append(min(first, run.window_s) - s.due)
    return out


def itl_s(run) -> list:
    out = []
    for s in run.served + run.carried:
        ts = [t for t in s.token_times if 0.0 <= t <= run.window_s]
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def ms_per(run, kinds: tuple) -> float | None:
    """Host milliseconds in the model fns per prompt token or decoded row."""
    calls = [c for c in run.calls if c.kind in kinds]
    n = sum(c.tokens for c in calls)
    if not n:
        return None
    return 1000.0 * sum(c.end - c.start for c in calls) / n


def decode_invocations(run, traced_only: bool = True) -> list:
    """Each ``decode_jit`` call as the positions of its rows: a decode_fn
    call makes one per group of rows at one position; a replay makes one
    per token."""
    out = []
    for c in run.calls:
        if traced_only and not run.in_trace(c):
            continue
        if c.kind == "decode":
            groups: dict = {}
            for p in c.positions:
                groups.setdefault(p, []).append(p)
            out += list(groups.values())
        elif c.kind == "replay":
            out += [[c.positions[0] + i] for i in range(c.tokens)]
    return out


def window_flops(run) -> float:
    """The algorithm's operations for every token processed in the window:
    prompt tokens (the output head on the last only) and decoded rows."""
    cfg, total = run.config, 0
    for c in run.calls:
        if c.kind == "prefill":
            total += C.prefill_flops(cfg, 0, c.tokens)
        elif c.kind == "replay":
            total += C.prefill_flops(cfg, c.positions[0], c.tokens)
        elif c.kind == "decode":
            total += sum(C.dense_token_flops(cfg, p) for p in c.positions)
    return total


def idle_share(run) -> float | None:
    d = run.device
    if not d or d["window_s"] <= 0 or d["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])

"""Arithmetic of the end-to-end serving metrics, over one window's records.

Times are seconds on the host's monotonic clock, measured from the start
of the window; the window is ``[0, window_s)``.  Each request record is a
``RequestLog``: when it was due under the open-loop schedule, whether it
failed, and the time each of its output tokens reached the caller.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class RequestLog:
    """What the harness saw of one request."""

    due: float
    token_times: list = dataclasses.field(default_factory=list)
    failed: bool = False
    admitted_at: float | None = None


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it.  Raises on an empty sequence."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return vals[rank - 1]


def ttfts(logs, window_s: float, drain_end: float) -> list[float]:
    """Time from due to first token of every request due in the window.

    A request that failed, or that had no first token by ``drain_end``,
    missed any limit: it counts as waiting until ``drain_end`` (a lower
    bound of its time, and above that of every request that answered).
    """
    out = []
    for r in logs:
        if r.due >= window_s:
            continue
        if r.failed or not r.token_times:
            out.append(max(drain_end, window_s) - r.due)
        else:
            out.append(r.token_times[0] - r.due)
    return out


def token_gaps(logs, window_s: float) -> list[float]:
    """Gaps between consecutive output tokens of one request, for every gap
    that closes inside the window.  Tokens returned by one engine step
    together have a gap of 0."""
    out = []
    for r in logs:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]) if b < window_s)
    return out


def tokens_in_window(logs, window_s: float) -> int:
    """Output tokens that reached the caller inside the window."""
    return sum(1 for r in logs for t in r.token_times if 0.0 <= t < window_s)


def serving_metrics(logs, window_s: float, drain_end: float) -> dict:
    """The end-to-end serving metrics of one window, in ms and tokens/s."""
    ttft = ttfts(logs, window_s, drain_end)
    gaps = token_gaps(logs, window_s)
    return {
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "itl_p99_ms": 1e3 * percentile(gaps, 99),
        "output_tok_s": tokens_in_window(logs, window_s) / window_s,
    }

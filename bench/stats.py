"""Metric arithmetic on the driver's host records.

Every time is host ms from the window's start. A request of an open mix
counts when it was due inside the window; one that has not finished by
the end of the loop has failed, and its latencies are taken up to that
end (a lower bound), so it still weighs on the tail.
"""
from __future__ import annotations

import numpy as np


def pct(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation), None when empty."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def median(values) -> float | None:
    return pct(values, 50)


def window_requests(records, seconds: float) -> list:
    """Open mix: the requests due inside the window."""
    end = seconds * 1e3
    return [r for r in records if r.planned.due_ms < end]


def ttft_ms(rec, stop_ms: float) -> float:
    """First token on the host minus the due time."""
    first = rec.first_ms if rec.first_ms is not None else stop_ms
    return first - rec.planned.due_ms


def tpot_ms(rec, stop_ms: float) -> float | None:
    """(last token - first token) / (tokens - 1)."""
    if rec.first_ms is None:
        return None
    last = rec.token_ms[-1] if rec.done else stop_ms
    n = rec.planned.output_len if rec.done else len(rec.token_ms)
    return (last - rec.first_ms) / max(n - 1, 1)


def queue_wait_ms(rec, stop_ms: float) -> float:
    """Due time to the start of admission."""
    start = rec.admit_ms if rec.admit_ms is not None else stop_ms
    return start - rec.planned.due_ms


def tokens_in(records, t0_ms: float, t1_ms: float) -> int:
    """Tokens delivered to the host in [t0, t1)."""
    return sum(sum(1 for t in r.token_ms if t0_ms <= t < t1_ms)
               for r in records)


def failed(records, seconds: float) -> list:
    return [r for r in window_requests(records, seconds) if not r.done]

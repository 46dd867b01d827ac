"""Shared by the decode-round readers: the round period, the interval
between consecutive harvests of ``RuntimeMetrics.round_ms`` readings
(each stamped when its tokens reached the host), for harvests inside
[t0, t1) ms of the window. ``round_ms`` itself runs from a round's
dispatch to its tokens on the host; with the executor's overlap the
round is dispatched behind its predecessor, so it spans about two
periods."""
from bench import stats


def periods(run, t0, t1) -> list[float]:
    stamps = [t for t, _ in run.rounds]
    return [b - a for a, b in zip(stamps, stamps[1:]) if t0 <= b < t1]


def median_round(run, t0, t1):
    return stats.median(periods(run, t0, t1))

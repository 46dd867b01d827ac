"""decode_round_ms.backlog: median round period (interval between
consecutive RuntimeMetrics.round_ms harvests) over the window
of a backlog mix (host clock)."""
from bench.metrics._rounds import median_round


def read(run):
    if run.kind != "backlog":
        return None
    return median_round(run, 0.0, run.seconds * 1e3)

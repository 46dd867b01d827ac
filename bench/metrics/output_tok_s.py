"""output_tok_s: tokens delivered to the host during the window over the
window's seconds (host clock). Only a backlog mix reads it: in an open
mix it would be the offered load."""
from bench import stats


def read(run):
    if run.kind != "backlog":
        return None
    return stats.tokens_in(run.records, 0.0, run.seconds * 1e3) \
        / run.seconds

"""setup_s: process start to window start, host clock: imports, weights
from the seed, parity encode, warm-up of every shape (and, in a backlog
mix, filling the slots)."""


def read(run):
    return run.setup_s

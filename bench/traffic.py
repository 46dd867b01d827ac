"""The one traffic generator: a mix file of fixed lists -> requests.

A mix (``bench/traffic/<name>.json``) holds its lengths as fixed lists:
8 prompt and 8 output lengths taken at fixed quantiles of a stated
distribution, a fixed ``order`` of (prompt index, output index) pairs
that interleaves them, and, for an open mix, a fixed list of ``gaps``
(mean 1) that the mix's ``rate_rps`` scales into arrival times. Nothing
here depends on the seed: the seed draws only the token ids of each
prompt.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

KINDS = ("open", "backlog")


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the mix plans it: same for every seed."""
    idx: int
    due_ms: float          # open: due time from window start; backlog: 0
    prompt_len: int
    output_len: int


def load_mix(traffic: str) -> dict:
    """The mix file ``bench/traffic/<traffic>.json``."""
    with open(os.path.join(BENCH_DIR, "traffic", f"{traffic}.json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {traffic}: kind must be one of {KINDS}")
    if mix["kind"] == "open" and "rate_rps" not in mix:
        raise ValueError(f"open traffic {traffic} needs rate_rps")
    return mix


def plan(mix: dict, horizon_s: float) -> list[Planned]:
    """An open mix's requests in their fixed order, every one due before
    ``horizon_s``."""
    order = mix["order"]
    pl, ol = mix["prompt"]["lengths"], mix["output"]["lengths"]
    out = []
    gaps = mix["gaps"]["values"]
    scale = 1e3 / float(mix["rate_rps"])
    due, i = 0.0, 0
    while due < horizon_s * 1e3:
        p, o = order[i % len(order)]
        out.append(Planned(i, due, pl[p], ol[o]))
        due += gaps[i % len(gaps)] * scale
        i += 1
    return out


def backlog_request(mix: dict, i: int) -> Planned:
    """The ``i``-th request of a backlog mix (no due time)."""
    p, o = mix["order"][i % len(mix["order"])]
    return Planned(i, 0.0, mix["prompt"]["lengths"][p],
                   mix["output"]["lengths"][o])


def prompt_tokens(seed: int, idx: int, length: int, vocab: int
                  ) -> np.ndarray:
    """Token ids of request ``idx``: drawn from (seed, idx) alone, so a
    request's prompt does not depend on how many others were drawn."""
    rng = np.random.default_rng([seed % 2 ** 63, idx])
    return rng.integers(0, vocab, length, dtype=np.int32)


def distinct_prompt_lengths(mix: dict) -> list[int]:
    return sorted(set(mix["prompt"]["lengths"]))


def quantile_lengths(dist: dict) -> list[int]:
    """The lengths a mix's stated distribution gives at its quantiles
    (i + 0.5) / n, rounded to ``round_to`` and clipped to [min, max]:
    what the fixed list in the file must equal."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {dist['dist']}")
    n = len(dist["lengths"])
    step = dist.get("round_to", 1)
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        v = dist["median"] * math.exp(dist["sigma"] * z)
        v = int(round(v / step) * step)
        out.append(min(max(v, dist["min"]), dist["max"]))
    return out

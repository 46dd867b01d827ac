"""A run's work does not depend on its seed: for every cell of
BENCHMARK.json, the planned requests are the same for every seed and
only the token ids differ."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import harness, traffic

SPEC = harness.load_spec()
CELLS = [c["name"] for c in SPEC["workloads"]]
MIXES = sorted(os.listdir(os.path.join(traffic.BENCH_DIR, "traffic")))
SEEDS = [0, 1, 7, 2 ** 31 - 1, 2 ** 31 + 5, 2 ** 32 + 3, 12345, 99,
         31337, 2 ** 40, 5, 424242]


def _cell(name):
    cell = harness.find_cell(SPEC, name)
    return cell, traffic.load_mix(cell["traffic"]), \
        harness.load_config(cell["config"])


@pytest.mark.parametrize("name", CELLS)
def test_plan_is_the_same_for_every_seed(name):
    cell, mix, cfg = _cell(name)
    seconds = SPEC["run_seconds"]
    plans = []
    prompts = []
    for seed in SEEDS:
        # the plan takes no seed at all; the tokens are the seed's only
        # draw, so the window's prompt and output tokens are the same
        if mix["kind"] == "open":
            plan = traffic.plan(mix, seconds + mix["drain_s"])
        else:
            plan = [traffic.backlog_request(mix, i) for i in range(256)]
        plans.append([(p.due_ms, p.prompt_len, p.output_len) for p in plan])
        prompts.append([traffic.prompt_tokens(seed, p.idx, p.prompt_len,
                                              cfg["hf"]["vocab_size"])
                        for p in plan[:8]])
    assert all(p == plans[0] for p in plans)
    for a in prompts[1:]:
        assert [len(x) for x in a] == [len(x) for x in prompts[0]]
        assert any(not np.array_equal(x, y) for x, y in zip(a, prompts[0]))
    if mix["kind"] == "open":
        window = [p for p in plans[0] if p[0] < seconds * 1e3]
        assert len(window) >= 40, "too few requests due for a tail"


@pytest.mark.parametrize("name", MIXES)
def test_mix_lengths_follow_the_stated_distribution(name):
    with open(os.path.join(traffic.BENCH_DIR, "traffic", name)) as f:
        mix = json.load(f)
    for key in ("prompt", "output"):
        dist = mix[key]
        assert dist["lengths"] == traffic.quantile_lengths(dist)
    assert len(traffic.distinct_prompt_lengths(mix)) <= 8
    pairs = {tuple(p) for p in mix["order"]}
    assert len(pairs) == len(mix["prompt"]["lengths"]) \
        * len(mix["output"]["lengths"])
    if mix["kind"] == "open":
        assert abs(np.mean(mix["gaps"]["values"]) - 1.0) < 1e-3


@pytest.mark.parametrize("name", CELLS)
def test_cell_fits_its_cache(name):
    cell, mix, cfg = _cell(name)
    longest = max(mix["prompt"]["lengths"]) + max(mix["output"]["lengths"])
    assert longest <= cfg["serving"]["max_len"]
    if mix["kind"] == "open":
        assert mix["rate_rps"] > 0


def test_token_ids_depend_on_seed_and_index_only():
    a = traffic.prompt_tokens(2 ** 33 + 1, 5, 64, 32000)
    b = traffic.prompt_tokens(2 ** 33 + 1, 5, 64, 32000)
    c = traffic.prompt_tokens(2 ** 33 + 1, 6, 64, 32000)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 32000


def test_backlog_cycles_through_its_order():
    with open(os.path.join(traffic.BENCH_DIR, "traffic", "decode.json")) as f:
        mix = json.load(f)
    n = len(mix["order"])
    first = [traffic.backlog_request(mix, i) for i in range(n)]
    again = [traffic.backlog_request(mix, i + n) for i in range(n)]
    assert [(p.prompt_len, p.output_len) for p in first] == \
        [(p.prompt_len, p.output_len) for p in again]

"""The comparison that decides ``correct``, driven end to end on the CPU
at a small size: a sound run passes, the int8 control and each fault of
``bench/faults.py`` fail it.

Each run skips only the harness's look for a chip: it builds the program
from the seed, warms it, drives the backlog loop that the chip's cell
runs through the scheduler and the slot-pool executor, and compares a
sample of the served requests, one from every slot, with the plain
float32 reference.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np
import pytest

from bench import faults, harness, traffic

#: the small configuration: the family's equations at toy widths. Its
#: limit sits between the readings of the backlog loop on the CPU (2 s
#: window, 16 sampled requests, 590-720 tokens compared): the sound
#: program's widest gap 0.015-0.024 and the int8 control's 0.049-0.125
#: (five seeds each; SEED read 0.020 and 0.088).
TINY = {
    "name": "tiny", "model": "llama_gqa",
    "hf": {"hidden_size": 256, "intermediate_size": 512,
           "num_hidden_layers": 4, "num_attention_heads": 8,
           "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 1024,
           "sliding_window": 64, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-5},
    "serving": {"n_slots": 4, "max_len": 128, "dtype": "bfloat16",
                "cache_dtype": "bfloat16",
                "code": {"T": 4, "r": 2, "layout": "folded"}},
    "correct": {"max_logit_gap": 0.04, "min_tokens": 200},
}
SEED = 2 ** 31 + 77


def _mix():
    with open(os.path.join(traffic.BENCH_DIR, "traffic", "decode.json")) as f:
        mix = json.load(f)
    mix["prompt"]["lengths"] = [8, 12, 16, 20, 24, 28, 32, 40]
    mix["output"]["lengths"] = [24, 28, 32, 40, 48, 56, 64, 80]
    return mix


def _run(tmp_path, patch=None, control=False, seed=SEED):
    spec = harness.load_spec()
    cell = {"name": "tiny.decode", "config": "tiny", "traffic": "decode",
            "chips": 1}
    return harness.run_cell(spec, cell, seed, 2.0, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), config=TINY, mix=_mix(),
                            control=control, patch=patch,
                            root=str(tmp_path))


def test_sound_run_passes_and_the_control_fails(tmp_path):
    res = _run(tmp_path, control=True)
    assert res["correct"], res["compared"]
    assert res["compared"]["tokens_compared"]["value"] >= 200
    ctrl = res["control"]
    assert not ctrl["correct"], ctrl
    gap = ctrl["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(tmp_path, fault):
    res = _run(tmp_path, patch=faults.FAULTS[fault])
    assert not res["correct"]
    assert res["compared"]["max_logit_gap"]["value"] \
        > res["compared"]["max_logit_gap"]["limit"]
    assert np.isfinite(res["compared"]["max_logit_gap"]["value"])

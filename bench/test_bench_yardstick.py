"""The yardstick: trace reduction on a synthetic trace, the kernels'
operations and bytes at real widths, the peaks table, and the harness's
refusal to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench import costs, harness
from bench.trace import Op, Span, Summary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _synthetic():
    ms = 1e-3
    spans = [Span("bench.step", 0.0, 10 * ms),
             Span("bench.round", 1 * ms, 4 * ms),
             Span("bench.admit", 4 * ms, 8 * ms, (("prompt_len", 512),)),
             Span("bench.idle", 8 * ms, 10 * ms)]
    ops = [Op("cdc_coded_matmul_pallas.3", "jit__round_fused", 0.0, 2 * ms),
           Op("cdc_coded_matmul_pallas.4", "jit__round_fused", 1 * ms,
              1 * ms),                                  # overlaps the first
           Op("cdc_fused_head_argmax_pallas", "jit__round_fused", 3 * ms,
              0.5 * ms),
           Op("while.7", "jit__lambda", 4.5 * ms, 2 * ms),    # the prefill
           Op("fusion.1", "jit__lambda", 4.6 * ms, 1 * ms),   # nested
           Op("copy.2", "jit__round_fused", 6.5 * ms, 0.5 * ms),
           Op("late", "jit__lambda", 9.5 * ms, 2 * ms)]  # past the window
    return Summary(ops, spans)


def test_busy_union_idle_share_and_gaps():
    s = _synthetic()
    assert s.window_s == pytest.approx(10e-3)
    # busy: [0,2] [3,3.5] [4.5,7] [9.5,10] = 2 + .5 + 2.5 + .5 ms
    assert s.busy_s == pytest.approx(5.5e-3)
    assert s.idle_share() == pytest.approx(0.45)
    gaps = s.gaps()
    assert [round(b - a, 6) for a, b in gaps] == [0.0025, 0.001, 0.001]
    names = [g[0] for g in s.breakdown()["idle_gaps"]]
    assert names[0] == "bench.idle"
    assert set(names[1:]) == {"bench.round", "bench.admit"}


def test_kernel_time_by_stable_name_and_admission_time():
    s = _synthetic()
    assert s.kernel("cdc_coded_matmul_pallas") == (pytest.approx(3e-3), 2)
    assert s.kernel("cdc_fused_head_argmax_pallas")[1] == 1
    top = s.breakdown()["device_ops"]
    assert top[0][0] == "jit__round_fused/cdc_coded_matmul_pallas.3"
    assert not any("while" in k for k, _ in top)


def test_empty_trace_reads_nothing():
    s = Summary([], [])
    assert s.idle_share() is None and s.kernel("x") == (0.0, 0)


#: h2o-danube-1.8b's published widths (arXiv:2401.16818), which no cell
#: runs yet
WIDTHS = {"danube-1.8b": {"hidden_size": 2560, "intermediate_size": 6912,
                          "num_hidden_layers": 24,
                          "num_attention_heads": 32,
                          "num_key_value_heads": 8, "vocab_size": 32000}}


@pytest.mark.parametrize("config,expect", [
    # rows 16, T 4, r 2: per layer wq wk wv w_gate w_up, then the head
    ("danube-1.8b", {"layers": 24, "head_cols": 8064,
                     "wq": (16, 2560, 640), "wk": (16, 2560, 160),
                     "ffn": (16, 2560, 1728)}),
    ("granite-3-8b-l8", {"layers": 8, "head_cols": 12416,
                         "wq": (16, 4096, 1024), "wk": (16, 4096, 256),
                         "ffn": (16, 4096, 3200)}),
])
def test_kernel_ops_and_bytes_at_real_widths(config, expect):
    hf = WIDTHS.get(config) or harness.load_config(config)["hf"]
    calls = costs.round_kernel_calls(hf, 16, 4, 2)
    mm = calls["cdc_coded_matmul_pallas"]
    assert len(mm) == 5 * expect["layers"]

    def by_hand(rows, k, m_l):
        ops = 2 * rows * k * m_l * 6
        nbytes = rows * k * 2 + 4 * k * m_l * 2 + 2 * k * m_l * 4 \
            + rows * 4 * m_l * 2 + 8 * m_l
        return ops, nbytes

    assert mm[0] == by_hand(*expect["wq"])
    assert mm[1] == mm[2] == by_hand(*expect["wk"])
    assert mm[3] == mm[4] == by_hand(*expect["ffn"])
    (head,) = calls["cdc_fused_head_argmax_pallas"]
    k, cols = hf["hidden_size"], expect["head_cols"]
    assert head == (2 * 16 * k * cols * 5,
                    16 * k * 4 + 4 * k * cols * 2 + k * cols * 4 + 16 * 8)
    pk = costs.peaks("TPU v5 lite")
    least, bound = costs.min_seconds(mm, pk)
    assert bound == "memory" and least > 0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")
    assert "source" in costs.peaks("TPU v5 lite")


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "granite-3-8b-l8.decode", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

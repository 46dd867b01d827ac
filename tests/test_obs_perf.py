"""Perf observability (``repro.obs.perf`` + ``repro.obs.history``).

Tier-1 properties: roofline attribution of the live executor rounds,
held against the v5e peaks the test names, gives a CPU-smoke
``roofline_utilization`` in (0, 1] (CPU is far slower than the TPU
bound) while an unnamed CPU reports no roofline share at all, the paper's Fig. 2 constant-cost claim holds as
a runtime metric (``parity_device_equiv`` flat across T at fixed r while
``coded_overhead_frac`` falls), the fused full-Pallas round reports
non-zero FLOPs within 5% of the reference round at r=1 (the Pallas
custom-call cost registry agrees with counted HLO dots), synthetic
TPU-style custom-call HLO is costed through the registry by
longest-name containment, the benchmark history appends/loads/compares
round-trip with a regression gate that fires on a synthetic 30% slowdown
and stays quiet within tolerance, perf counter events validate as a
Perfetto counter track, disabled tracing emits nothing, and the live
``MetricsServer`` answers ``/healthz`` and exposes ``repro_perf_*``
gauges.
"""
import json
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_arch, smoke_config
from repro.models import TPCtx, build
from repro.obs import (FlightRecorder, MetricsServer, chrome_trace,
                       prometheus_text, validate_chrome_trace)
from repro.obs.history import (append_snapshot, check_history, compare,
                               load_history, make_snapshot)
from repro.obs.perf import PerfMonitor, attribute_round_costs
from repro.roofline.analysis import PEAKS, device_peaks
from repro.roofline.hlo_cost import analyze_hlo
from repro.runtime import (ContinuousBatchingScheduler, RuntimeConfig,
                           run_arrivals)
from repro.runtime.executor import SlotPoolExecutor
from repro.serve import ModelStepper

GEN = 4
PROMPT_LEN = 8
V5E = PEAKS["TPU v5 lite"]


def _stepper(tp=4, code_r=1, arch="granite-3-8b"):
    cfg = smoke_config(get_arch(arch))
    model = build(cfg, TPCtx(tp=tp, mode="coded", code_r=code_r,
                             moe_capacity=0))
    params = model.init(jax.random.PRNGKey(0))
    return cfg, ModelStepper(model, params, max_len=32)


def _perf_scheduler(stepper, tracer=None, hw=V5E):
    """A perf-accounting scheduler whose monitor holds rounds against
    ``hw`` (the CPU has no peaks of its own)."""
    sched = ContinuousBatchingScheduler(
        stepper, RuntimeConfig(n_slots=2, perf=True), tracer=tracer)
    sched.executor.perf.hw = hw
    return sched


def _workload(cfg, n=3, span_ms=150.0):
    rng = np.random.default_rng(7)
    gap = span_ms / max(n, 1)
    return [(i * gap, rng.integers(0, cfg.vocab, PROMPT_LEN), GEN)
            for i in range(n)]


def _costs(tp, code_r, use_fused=False):
    _, stepper = _stepper(tp=tp, code_r=code_r)
    ex = SlotPoolExecutor(stepper, 2, use_fused=use_fused)
    return attribute_round_costs(ex.vstep, ex.state, ex.last_toks)


# ------------------------------------------------------- attribution ----

@pytest.fixture(scope="module")
def perf_run():
    """One CPU smoke serve with perf accounting + tracing on."""
    cfg, stepper = _stepper()
    tracer = FlightRecorder()
    sched = _perf_scheduler(stepper, tracer)
    run_arrivals(sched, _workload(cfg))
    return sched, tracer


def test_utilization_in_unit_interval_on_cpu(perf_run):
    sched, _ = perf_run
    perf = sched.executor.perf
    assert perf.n_observed > 0
    s = perf.summary()
    # the roofline bound models the TPU HW target; a CPU round is orders
    # of magnitude slower, so utilization must land strictly inside (0, 1]
    assert 0.0 < s["roofline_utilization"] <= 1.0
    assert s["achieved_flops_per_s"] > 0
    assert s["hbm_gbs"] > 0
    assert s["model_flops"] > 0
    assert s["parity_flops"] >= 0
    # merged into the runtime metrics for the Prometheus gauges
    assert sched.metrics.perf["roofline_utilization"] == \
        s["roofline_utilization"]
    assert sched.metrics.perf["n_rounds_observed"] == perf.n_observed


def test_no_roofline_share_without_peaks():
    """Off the TPU the monitor has no peaks of its own: the CPU run still
    reports counts and achieved rates, but no roofline share or bound."""
    if jax.default_backend() == "tpu":
        pytest.skip("the chip has peaks of its own")
    assert device_peaks() is None
    cfg, stepper = _stepper()
    sched = _perf_scheduler(stepper, hw=None)
    run_arrivals(sched, _workload(cfg, n=2))
    s = sched.executor.perf.summary()
    assert sched.executor.perf.n_observed > 0 and s["model_flops"] > 0
    for key in ("roofline_utilization", "bound_step_us", "dominant"):
        assert key not in s and key not in sched.metrics.perf


def test_unknown_tpu_kind_has_no_peaks():
    """A TPU kind missing from the table is an error, not a default."""
    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    with pytest.raises(KeyError, match="TPU v99"):
        device_peaks(Dev())

    class V5e:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert device_peaks(V5e()) is V5E


def test_parity_device_equiv_flat_across_T():
    """Fig. 2 as a runtime metric: at fixed r the parity work equals ~r
    device-equivalents of one shard's useful work, independent of T —
    while parity/total (coded_overhead_frac) falls as T grows."""
    c2 = _costs(tp=2, code_r=1)["reference"]
    c4 = _costs(tp=4, code_r=1)["reference"]
    assert c2.T == 2 and c4.T == 4 and c2.r == c4.r == 1
    assert c2.parity_device_equiv > 0 and c4.parity_device_equiv > 0
    rel = abs(c4.parity_device_equiv - c2.parity_device_equiv) \
        / c2.parity_device_equiv
    assert rel < 0.10, (c2.parity_device_equiv, c4.parity_device_equiv)
    # the naive parity/total ratio is NOT flat: it shrinks with T
    assert c4.coded_overhead_frac < c2.coded_overhead_frac


def test_fused_round_flops_within_5pct_of_reference():
    """The Pallas custom-call cost registry must agree with counted HLO
    dots: at r=1 the fused round (sum-parity head, T+1 GEMMs) does the
    same work as the reference round (T+r GEMMs)."""
    costs = _costs(tp=4, code_r=1, use_fused=True)
    assert set(costs) == {"reference", "fused"}
    ref, fused = costs["reference"], costs["fused"]
    assert fused.flops > 0, "fused round reported zero FLOPs"
    assert abs(fused.flops / ref.flops - 1.0) < 0.05, (fused.flops,
                                                       ref.flops)
    # both variants attribute against the same plain-model useful FLOPs
    assert fused.useful_flops == ref.useful_flops > 0


# --------------------------------------------- custom-call cost model ----

_SYNTH_HLO = """\
HloModule synth

ENTRY %main (p0: f32[8,64], p1: f32[4,64,16], p2: f32[1,64,16]) -> f32[8,4,16] {
  %p0 = f32[8,64]{1,0} parameter(0)
  %p1 = f32[4,64,16]{2,1,0} parameter(1)
  %p2 = f32[1,64,16]{2,1,0} parameter(2)
  %unk = f32[8,16]{1,0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/jit(mystery_kernel)/pallas_call"}
  ROOT %cc = f32[8,4,16]{2,1,0} custom-call(%p0, %p1, %p2), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/jit(cdc_coded_matmul_pallas)/pallas_call"}
}
"""


def test_synthetic_custom_call_is_costed_via_registry():
    """TPU-style opaque custom-calls: the registry models the coded-GEMM
    kernel ((T+r) shard GEMMs) and counts the unknown kernel as uncosted
    instead of silently reporting ~0 FLOPs."""
    res = analyze_hlo(_SYNTH_HLO)
    # out [rows=8, T=4, m_l=16], w_shards [4,64,16] -> k=64, parity [1,..]
    assert res["flops"] == 2.0 * 8 * 64 * 16 * (4 + 1)
    assert res["custom_calls_costed"] == 1
    assert res["custom_calls_uncosted"] == 1


def test_registry_longest_name_containment():
    """``matmul_pallas`` is a substring of ``cdc_coded_matmul_pallas``:
    the longer (exact) kernel name must win the match."""
    res = analyze_hlo(_SYNTH_HLO)
    # the plain-matmul model on a rank-3 output would return 0.0 (shape
    # guard) — the (T+r)-GEMM result proves the coded model was chosen
    assert res["flops"] > 0


def test_interpret_and_registry_costs_agree():
    """CPU interpret mode inlines the kernels into real HLO dots; forcing
    the fused path there must therefore report comparable FLOPs to what
    the registry models for the native custom-call (same 5% band the
    fused-vs-reference check relies on)."""
    costs = _costs(tp=2, code_r=1, use_fused=True)
    assert abs(costs["fused"].flops / costs["reference"].flops - 1) < 0.05


# ------------------------------------------------------------ history ----

def test_history_append_load_roundtrip(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    rec = append_snapshot(path, "serve_throughput", "granite-3-8b",
                          {"rounds_per_s": 100.0, "model_flops": 1e6,
                           "skipme": None})
    assert rec["schema"] == 1 and rec["git_sha"]
    assert "skipme" not in rec["metrics"]
    append_snapshot(path, "serve_throughput", "granite-3-8b",
                    {"rounds_per_s": 101.0, "model_flops": 1e6})
    loaded = load_history(path)
    assert [r["metrics"]["rounds_per_s"] for r in loaded] == [100.0, 101.0]
    # unparsable lines and newer-schema records are skipped, not fatal
    with open(path, "a") as f:
        f.write("not json\n")
        f.write(json.dumps({"schema": 99, "metrics": {}}) + "\n")
    assert len(load_history(path)) == 2


def test_compare_quiet_within_tolerance_and_fires_beyond():
    base = [make_snapshot("b", "a", {"rounds_per_s": 100.0,
                                     "ttft_p99_ms": 50.0,
                                     "model_flops": 1e6})
            for _ in range(5)]
    ok = make_snapshot("b", "a", {"rounds_per_s": 90.0,   # -10% < 25% tol
                                  "ttft_p99_ms": 55.0,
                                  "model_flops": 1e6})
    assert compare(ok, base) == []
    bad = make_snapshot("b", "a", {"rounds_per_s": 60.0,  # -40% regression
                                   "ttft_p99_ms": 120.0,  # +140% regression
                                   "model_flops": 2e6})   # drifted
    names = {r["metric"] for r in compare(bad, base)}
    assert names == {"rounds_per_s", "ttft_p99_ms", "model_flops"}


def test_regression_gate_fires_on_synthetic_slowdown(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    for v in (100.0, 102.0, 98.0):
        append_snapshot(path, "serve_throughput", "granite-3-8b",
                        {"rounds_per_s": v, "ttft_p99_ms": 50.0})
    # within tolerance: the last record vs its predecessors is quiet
    results = check_history(path)
    assert len(results) == 1 and results[0]["regressions"] == []
    # a 30% synthetic slowdown MUST trip the 25% rounds_per_s tolerance
    fired = check_history(path, inject_slowdown=0.30)
    assert any(r["regressions"] for r in fired)
    metrics = {reg["metric"] for r in fired for reg in r["regressions"]}
    assert "rounds_per_s" in metrics
    # CLI exit codes mirror that (what the CI gate asserts on)
    from repro.obs.history import main as history_main
    assert history_main(["check", "--path", path]) == 0
    assert history_main(["check", "--path", path,
                         "--inject-slowdown", "0.30"]) == 1


# ----------------------------------------------------- trace + gauges ----

def test_perf_counter_track_validates(perf_run):
    _, tracer = perf_run
    kinds = {e.kind for e in tracer.events()}
    assert "perf.attribution" in kinds and "perf.counter" in kinds
    trace = chrome_trace(tracer)
    stats = validate_chrome_trace(trace, require_perf_counters=True)
    assert stats["n_perf_counters"] > 0
    # counter events carry numeric-only args (Perfetto charts them)
    counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
    assert counters
    for ev in counters:
        assert ev["args"]
        assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   for v in ev["args"].values())


def test_validate_requires_perf_counters_when_asked():
    rec = FlightRecorder()
    rec.emit("round.dispatch", track="rounds", round=0, n_active=1, dead=[])
    with pytest.raises(ValueError, match="perf"):
        validate_chrome_trace(chrome_trace(rec), require_perf_counters=True)


def test_perf_without_tracer_emits_nothing():
    """Perf accounting with tracing disabled: gauges still update, but the
    NULL recorder records zero events (and its emit is a no-op branch)."""
    cfg, stepper = _stepper()
    sched = _perf_scheduler(stepper)
    run_arrivals(sched, _workload(cfg, n=2))
    assert sched.executor.perf.n_observed > 0
    assert sched.metrics.perf["roofline_utilization"] > 0
    assert not sched.tracer.enabled
    assert list(sched.tracer.events()) == []


def test_metrics_server_healthz_and_perf_gauges(perf_run):
    sched, tracer = perf_run
    text = prometheus_text(sched.metrics, sched.shardlog,
                           now_ms=sched.clock.now())
    assert "repro_perf_roofline_utilization" in text
    assert "repro_perf_coded_overhead_frac" in text
    server = MetricsServer(sched.metrics, sched.shardlog, tracer,
                           sched.clock, port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
            assert r.read() == b"ok\n"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "repro_perf_achieved_flops_per_s" in body
    finally:
        server.stop()

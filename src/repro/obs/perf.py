"""Perf observability: roofline-anchored cost attribution for live rounds.

The static ``roofline/`` cost model and the measured round latency in
``RuntimeMetrics.round_ms`` existed side by side but never met: the
runtime could say a round took 4.1 ms and the roofline could say a round
*should* take 0.9 ms, and nothing connected them. ``PerfMonitor`` closes
the loop, per dispatch:

  * **Attribution** (once per code geometry): lower + compile each live
    round variant the executor owns — ``reference`` (full-logits coded
    decode) and ``fused`` (full-Pallas round) — and run
    ``roofline.hlo_cost.analyze_hlo`` over the compiled HLO for
    FLOPs / HBM bytes / wire bytes per dispatch. The same state/params
    are also compiled through the PLAIN (uncoded) model — KV state is
    code-geometry independent, so the coded executor state drives the
    plain trace directly — giving ``useful_flops``; the difference is
    the parity work the code adds:

        coded_overhead_frac = parity_flops / total_flops
                            ≈ r/(T+r) · gemm_share   (falls with T)
        parity_device_equiv = parity_flops / (useful_flops / T)
                            ≈ r · gemm_share         (FLAT in T)

    ``parity_device_equiv`` is the paper's Fig. 2 constant-cost claim as
    a runtime metric: the parity work equals ~r extra devices' worth of
    one shard's useful work, independent of cluster width T.
  * **Utilization** (every harvest): combine the static per-round cost
    with the MEASURED round wall time from ``pool.py`` into
    ``achieved_flops_per_s``, ``hbm_gbs`` and ``roofline_utilization``
    (= roofline-bound step time / measured time, so 1.0 means the round
    runs exactly at the modelled hardware bound; reported only against
    known peaks — the chip's row of ``roofline.analysis.PEAKS``, or the
    ``hw`` a caller passes — never for a CPU by default). Published three
    ways: ``RuntimeMetrics.perf`` (-> Prometheus gauges), ``perf.counter``
    events on the flight recorder's ``perf`` track (dual-stamped:
    deterministic args carry the static cost, wall-derived values ride in
    ``wall_args`` so traced chaos runs still replay bit-exact), and
    ``summary()`` rows for the benchmarks / ``BENCH_history.jsonl``.

Pallas custom-call kernels are costed via ``kernels.ops.KERNEL_COSTS``
(see ``roofline/hlo_cost.py``); off-TPU interpret mode inlines the kernel
bodies into ordinary dots, so both paths report comparable FLOPs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs.tracer import NULL_RECORDER
from repro.roofline.analysis import device_peaks, roofline_terms
from repro.roofline.hlo_cost import analyze_hlo


@dataclasses.dataclass(frozen=True)
class RoundCost:
    """Static per-dispatch cost of one compiled round variant."""
    variant: str
    flops: float                 # total HLO FLOPs per dispatch
    bytes: float                 # HBM bytes per dispatch
    wire_bytes: float
    useful_flops: float          # the plain (uncoded) model's FLOPs
    parity_flops: float          # flops - useful_flops (>= 0)
    coded_overhead_frac: float   # parity / total: falls as T grows
    parity_device_equiv: float   # parity / (useful / T): flat in T (Fig. 2)
    T: int
    r: int
    bound_step_s: float | None   # roofline-bound round time on `hw`
    dominant: str | None         # compute | memory | collective (None:
    #                              no peaks for this device, e.g. a CPU)
    custom_calls_uncosted: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _analyze(lowerable, *args) -> dict:
    return analyze_hlo(lowerable.lower(*args).compile().as_text())


def _plain_round_flops(stepper, state, toks) -> float:
    """Useful FLOPs: the identical round through the PLAIN model with the
    RAW (uncoded) params. Slot state (KV caches, positions, extras) is
    code-mode independent, so the executor's stacked state compiles
    against the plain decode unchanged."""
    model = stepper.model
    pmodel = dataclasses.replace(
        model, ctx=dataclasses.replace(model.ctx, mode="plain",
                                       fused_body=False))

    def _round(params, state, toks):
        logits, new_state = pmodel.decode(params, state, toks, None)
        last = logits[:, -1:]
        return new_state, jnp.argmax(last, axis=-1).astype(jnp.int32), last

    return _analyze(jax.jit(_round), stepper.params, state,
                    toks)["flops"]


def attribute_round_costs(vstep, state, toks, hw: dict | None = None
                          ) -> dict[str, RoundCost]:
    """Cost every compiled round variant of ``vstep`` over the given slot
    state. Returns {variant: RoundCost} — always ``reference``, plus
    ``fused`` when the executor dispatches the full-Pallas round. The
    roofline bound is computed only against ``hw`` peaks; without them it
    stays None."""
    st = vstep.stepper
    coded = bool(st.coded)
    T = int(st.n_shards)
    r = int(st.model.ctx.code_r) if coded else 0
    valid = st._mask(st.full_mask()) if coded else None

    raw: dict[str, dict] = {
        "reference": _analyze(vstep._round, st.params, state, toks, valid)}
    if vstep.use_fused and coded:
        w_shards, parity_w = vstep._head_shards()
        raw["fused"] = _analyze(vstep._round_fused, st.params, state, toks,
                                valid, w_shards, parity_w)

    useful = raw["reference"]["flops"] if not coded \
        else _plain_round_flops(st, state, toks)

    out: dict[str, RoundCost] = {}
    for variant, cost in raw.items():
        flops = float(cost["flops"])
        parity = max(flops - useful, 0.0)
        terms = roofline_terms(
            {"flops": flops, "bytes accessed": cost["bytes"]},
            {"total": cost["wire_bytes"]}, hw) if hw else {}
        out[variant] = RoundCost(
            variant=variant, flops=flops, bytes=float(cost["bytes"]),
            wire_bytes=float(cost["wire_bytes"]), useful_flops=float(useful),
            parity_flops=parity,
            coded_overhead_frac=parity / flops if flops else 0.0,
            parity_device_equiv=(parity / (useful / T)
                                 if coded and useful else 0.0),
            T=T, r=r, bound_step_s=terms.get("bound_step_s"),
            dominant=terms.get("dominant"),
            custom_calls_uncosted=float(
                cost.get("custom_calls_uncosted", 0.0)))
    return out


def _roofline_fields(cost: RoundCost) -> dict:
    """The roofline bound and its limiting term, when peaks were known."""
    if cost.bound_step_s is None:
        return {}
    return {"bound_step_us": cost.bound_step_s * 1e6,
            "dominant": cost.dominant}


class PerfMonitor:
    """Per-round achieved-vs-roofline accounting for a slot-pool executor.

    Wired by ``SlotPoolExecutor`` when ``RuntimeConfig.perf`` is on:
    attribution runs lazily at the first harvest (the round is already
    compiled and warm) and re-runs whenever the planner's ``set_code_r``
    changes the (T, r) geometry; every harvest then feeds the measured
    round period through ``observe_round``.
    """

    def __init__(self, metrics=None, tracer=None, hw: dict | None = None):
        self.metrics = metrics
        self.tracer = tracer if tracer is not None else NULL_RECORDER
        # the chip's own peaks; off the TPU no roofline share is reported
        # unless the caller names the hardware to hold the round against
        self.hw = hw if hw is not None else device_peaks()
        self.costs: dict[str, RoundCost] = {}
        self.n_observed = 0
        self.last_variant: str | None = None
        self.last_round_ms: float | None = None
        self._geom: tuple[int, int] | None = None

    # ------------------------------------------------------- attribution ----
    def attribute(self, executor) -> dict[str, RoundCost]:
        st = executor.stepper
        self.costs = attribute_round_costs(
            executor.vstep, executor.state, executor.last_toks, hw=self.hw)
        self._geom = (int(st.n_shards),
                      int(st.model.ctx.code_r) if st.coded else 0)
        if self.tracer.enabled:
            for cost in self.costs.values():
                # deterministic: everything here comes from compiled HLO
                self.tracer.emit(
                    "perf.attribution", track="perf",
                    variant=cost.variant, flops=cost.flops,
                    hbm_bytes=cost.bytes, wire_bytes=cost.wire_bytes,
                    useful_flops=cost.useful_flops,
                    parity_flops=cost.parity_flops,
                    coded_overhead_frac=cost.coded_overhead_frac,
                    parity_device_equiv=cost.parity_device_equiv,
                    T=cost.T, r=cost.r, **_roofline_fields(cost))
        if self.metrics is not None:
            self.metrics.set_perf(self._static_summary())
        return self.costs

    def _maybe_attribute(self, executor):
        st = executor.stepper
        geom = (int(st.n_shards),
                int(st.model.ctx.code_r) if st.coded else 0)
        if geom != self._geom:
            self.attribute(executor)

    # -------------------------------------------------------- observation ----
    def observe_round(self, executor, wall_ms: float, variant: str):
        """One harvested round: measured period ``wall_ms`` for the round
        ``variant`` that was dispatched."""
        self._maybe_attribute(executor)
        cost = self.costs.get(variant) or self.costs.get("reference")
        if cost is None or wall_ms <= 0:
            return
        self.n_observed += 1
        self.last_variant = variant
        self.last_round_ms = float(wall_ms)
        derived = self.derived(cost, wall_ms)
        if self.metrics is not None:
            self.metrics.set_perf({"variant": variant,
                                   "n_rounds_observed": self.n_observed,
                                   **derived})
        if self.tracer.enabled:
            # counter-track sample: deterministic values in args (Perfetto
            # renders them as counter series), measured ones quarantined in
            # wall_args so replay comparison stays exact
            self.tracer.emit(
                "perf.counter", track="perf",
                variant=variant,
                model_gflops=cost.useful_flops / 1e9,
                coded_overhead_frac=cost.coded_overhead_frac,
                parity_device_equiv=cost.parity_device_equiv,
                wall_args={
                    "round_ms": wall_ms,
                    "achieved_gflops_per_s":
                        derived["achieved_flops_per_s"] / 1e9,
                    "hbm_gbs": derived["hbm_gbs"],
                    **({"roofline_utilization":
                        derived["roofline_utilization"]}
                       if "roofline_utilization" in derived else {})})

    def derived(self, cost: RoundCost, round_ms: float) -> dict:
        """Achieved rates for one measured round period."""
        s = round_ms / 1e3
        out = {
            "achieved_flops_per_s": cost.flops / s,
            "hbm_gbs": cost.bytes / s / 1e9,
            "round_ms": float(round_ms),
        }
        if cost.bound_step_s is not None:
            out["roofline_utilization"] = cost.bound_step_s / s
        return out

    # ------------------------------------------------------------ reading ----
    def _headline(self) -> RoundCost | None:
        if not self.costs:
            return None
        return self.costs.get(self.last_variant or "") \
            or self.costs.get("reference") \
            or next(iter(self.costs.values()))

    def _static_summary(self) -> dict:
        cost = self._headline()
        if cost is None:
            return {}
        return {
            "model_flops": cost.useful_flops,
            "hlo_flops": cost.flops,
            "hbm_bytes": cost.bytes,
            "wire_bytes": cost.wire_bytes,
            "parity_flops": cost.parity_flops,
            "coded_overhead_frac": cost.coded_overhead_frac,
            "parity_device_equiv": cost.parity_device_equiv,
            **_roofline_fields(cost),
            "T": cost.T, "r": cost.r,
            "custom_calls_uncosted": cost.custom_calls_uncosted,
        }

    def summary(self, round_ms: float | None = None) -> dict:
        """One flat report row: static attribution + achieved rates at
        ``round_ms`` (a steady-state p50 from the bench; defaults to the
        last observed round)."""
        cost = self._headline()
        if cost is None:
            return {}
        out = self._static_summary()
        out["variant"] = cost.variant
        out["n_rounds_observed"] = self.n_observed
        ms = round_ms if round_ms else self.last_round_ms
        if ms:
            out.update(self.derived(cost, ms))
        out["variants"] = {k: v.as_dict() for k, v in self.costs.items()}
        return out

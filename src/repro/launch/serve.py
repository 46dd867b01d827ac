"""Serving driver: runtime-scheduled generation with CDC fault injection.

Drives the coded cluster runtime (``repro.runtime``): requests are
submitted to the continuous-batching scheduler — the BATCHED slot
executor advances every decode slot in one jitted dispatch per round for
EVERY zoo architecture (enc-dec requests carry per-request encoder
frames into the stacked extras bank; xLSTM stacks its positionless block
state) — and a shard erasure can be injected at a simulated time; within
the code's budget the runtime recovers in-step, beyond it the CDC+2MR
hybrid requeues and heals.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \\
      --coded --fail-time-ms 4 --fail-shard 2
  PYTHONPATH=src python -m repro.launch.serve --arch whisper-medium \\
      --smoke --coded --fail-time-ms 4 --fail-shard 2

``--sequential`` keeps the per-slot stepping alive as the test oracle /
escape hatch (it is no longer the production path for any family),
``--no-overlap`` disables host/device round pipelining, ``--deadline-ms``
and ``--max-queue-depth`` exercise the SLO admission queue. ``--legacy``
runs the old one-batch-at-a-time ServingEngine path with the original
--fail-step semantics.

Chaos mode (``repro.faults``): ``--chaos <spec|trace>`` drives the health
controller with a seeded churn process (e.g.
``--chaos "weibull:mtbf=2000,mttr=120"`` — scale MTBF against the ~50 ms
modelled round floor) or a JSONL trace file, with the modelled round
latency following the same fault schedule; ``--adapt-r`` closes the loop
with the adaptive redundancy planner (re-sizes r through heal + parity
re-encode to hold ``--avail-target``). ``--seed`` is the root seed: the
whole chaos run replays bit-exact.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \\
      --coded --chaos "exp:mtbf=800,mttr=120" --adapt-r
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.core.failure import StragglerModel
from repro.faults import (AdaptiveRedundancyPlanner, InjectedLatency,
                          LatencySpec, PlannerConfig, attach_chaos,
                          attach_planner, measured_stall_hook, parse_chaos)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import TPCtx, build
from repro.obs import (FlightRecorder, MetricsServer, validate_chrome_trace,
                       write_chrome_trace)
from repro.runtime import (ContinuousBatchingScheduler, RuntimeConfig,
                           ShardHealthController, erasure, run_arrivals)
from repro.serve import ModelStepper, ServeConfig, ServingEngine


def _legacy(args, model, params):
    eng = ServingEngine(model, params,
                        ServeConfig(max_len=args.prompt_len
                                    + args.gen_tokens + 8, batch=args.batch,
                                    cache_dtype=jnp.float32))
    batch = model.dummy_batch(jax.random.PRNGKey(1), args.batch,
                              args.prompt_len)
    fail_at = {args.fail_step: args.fail_shard} if args.fail_step >= 0 \
        else None
    toks = eng.generate(batch, args.gen_tokens, fail_at=fail_at)
    print("generated tokens (first sequence):", toks[0].tolist())
    print("engine metrics:", eng.metrics)
    if args.coded:
        print("straggler model (first-T-of-T+r):",
              eng.straggler_latency(StragglerModel(), n_trials=5000))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--coded", action="store_true")
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="parameter and KV-cache dtype")
    ap.add_argument("--batch", type=int, default=2,
                    help="runtime: decode slots; legacy: batch size")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--arrival-gap-ms", type=float, default=2.0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--fail-time-ms", type=float, default=-1.0,
                    help="inject a shard erasure at this simulated time")
    ap.add_argument("--fail-shard", type=int, default=1)
    ap.add_argument("--fail-step", type=int, default=-1,
                    help="legacy mode: decode step to kill the shard at")
    ap.add_argument("--legacy", action="store_true")
    ap.add_argument("--sequential", action="store_true",
                    help="oracle-only per-slot stepping (one dispatch per "
                         "slot) instead of the batched executor; every "
                         "family — enc-dec and xLSTM included — batches "
                         "by default")
    ap.add_argument("--no-overlap", action="store_true",
                    help="harvest each round synchronously (no pipelining)")
    ap.add_argument("--fused", action="store_true",
                    help="force the full-Pallas round: fused in-body coded "
                         "GEMM+decode kernels and the fused head (interpret "
                         "off-TPU; default auto = native TPU only)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO deadline after arrival")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="shed requests beyond this queue depth")
    ap.add_argument("--chaos", default=None, metavar="SPEC|TRACE",
                    help="fault injection: churn spec "
                         "('weibull:mtbf=2000,mttr=120,groups=2,"
                         "burst_mtbf=4000') or a JSONL trace path")
    ap.add_argument("--adapt-r", action="store_true",
                    help="adaptive redundancy planner: re-size r from "
                         "observed failures (heal + parity re-encode)")
    ap.add_argument("--avail-target", type=float, default=0.999,
                    help="planner availability target")
    ap.add_argument("--plan-window-ms", type=float, default=300.0,
                    help="planner estimation window (sim time; several "
                         "decode rounds, ~50 ms each under the default "
                         "straggler floor)")
    ap.add_argument("--seed", type=int, default=0,
                    help="root seed: stragglers, injector, and injected "
                         "latency all derive from it (bit-exact replay)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the flight recorder and write a "
                         "Perfetto/Chrome trace_event JSON (open it at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus text metrics at "
                         "/metrics (and the trace at /trace) on this "
                         "port; 0 binds an ephemeral port")
    ap.add_argument("--slo-report", action="store_true",
                    help="print the per-request SLO breakdown after the "
                         "run: p50/p99 TTFT/TPOT decomposition tables and "
                         "deadline-miss attribution (same renderer as "
                         "python -m repro.obs.slo report)")
    ap.add_argument("--perf", action="store_true",
                    help="roofline-anchored round attribution: useful vs "
                         "parity FLOPs, live coded_overhead_frac, achieved "
                         "vs roofline utilization (auto-enabled with "
                         "--trace/--metrics-port/--profile)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR "
                         "(rounds annotated as decode_round steps; open "
                         "with TensorBoard or Perfetto)")
    return ap


def build_model(args):
    """(cfg, model, params) for ``args``: the registry config (cut to the
    smoke config with ``--smoke``), random params from a fixed key."""
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    ctx = TPCtx(tp=args.tp, mode="coded" if args.coded else "plain",
                moe_capacity=0)
    model = build(cfg, ctx)
    # one compiled program: its f32 temporaries fuse into the outputs,
    # where eager init would hold each layer stack's in f32 at once
    params = jax.jit(model.init, static_argnums=1)(
        jax.random.PRNGKey(0), jnp.dtype(args.dtype))
    return cfg, model, params


@dataclasses.dataclass
class Serving:
    """The serving stack ``build_serving`` wires for one run."""
    stepper: ModelStepper
    sched: ContinuousBatchingScheduler
    injector: Any = None
    tracer: FlightRecorder | None = None
    server: MetricsServer | None = None


def build_serving(args, model, params) -> Serving:
    """Stepper, health controller (with the ``--fail-time-ms`` erasure),
    continuous-batching scheduler over the slot-pool executor, and the
    optional chaos, planner and metrics-server attachments."""
    stepper = ModelStepper(model, params,
                           max_len=args.prompt_len + args.gen_tokens + 8,
                           cache_dtype=jnp.dtype(args.dtype))
    events = [erasure(args.fail_time_ms, args.fail_shard)] \
        if args.fail_time_ms >= 0 else []
    health = ShardHealthController(stepper.n_shards, stepper.erasure_budget,
                                   events=events)
    # perf accounting rides along whenever any observability sink is on:
    # the counter track needs it for --trace, the gauges for --metrics-port
    perf = bool(args.perf or args.trace or args.metrics_port is not None
                or args.profile)
    rcfg = RuntimeConfig(n_slots=args.batch,
                         batched=False if args.sequential else None,
                         overlap=not args.no_overlap,
                         use_fused=True if args.fused else "auto",
                         max_queue_depth=args.max_queue_depth,
                         seed=args.seed, perf=perf,
                         profile=args.profile is not None)
    injector = latency = None
    if args.chaos:
        injector = parse_chaos(args.chaos, stepper.n_shards, seed=args.seed)
        latency = InjectedLatency(LatencySpec(), injector, seed=args.seed)
    tracer = FlightRecorder() \
        if args.trace or args.metrics_port is not None else None
    sched = ContinuousBatchingScheduler(stepper, rcfg, health=health,
                                        latency=latency, tracer=tracer)
    server = None
    if args.metrics_port is not None:
        server = MetricsServer(sched.metrics, sched.shardlog, tracer,
                               sched.clock, port=args.metrics_port,
                               spans=sched.spans).start()
        print(f"metrics: http://127.0.0.1:{server.port}/metrics "
              f"(live trace: /trace)")
    if injector is not None:
        attach_chaos(sched, injector)
        if sched.executor is not None:
            sched.executor.round_hooks.append(measured_stall_hook(latency))
    if args.adapt_r:
        planner = AdaptiveRedundancyPlanner(
            PlannerConfig(target_availability=args.avail_target,
                          window_ms=args.plan_window_ms),
            stepper.n_shards, layout=model.ctx.code_layout,
            suitable=stepper.erasure_budget > 0 or not args.coded)
        attach_planner(sched, planner)
    return Serving(stepper, sched, injector, tracer, server)


def serve_requests(args, cfg, sched) -> list:
    """Submit ``--requests`` random prompts (timed arrivals, or all at
    once against ``--deadline-ms``) and run the scheduler until drained."""
    rng = np.random.default_rng(1)

    def extras():
        # enc-dec: per-request encoder frames (frontend stub) — threaded
        # into the executor's stacked extras bank at admission
        if not cfg.is_encdec:
            return None
        return {"frames": rng.normal(
            size=(cfg.enc_seq, cfg.d_model)).astype(np.float32)}

    if args.profile:
        jax.profiler.start_trace(args.profile)
    if args.deadline_ms is not None:
        for i in range(args.requests):
            t = i * args.arrival_gap_ms
            sched.submit(rng.integers(0, cfg.vocab, args.prompt_len),
                         args.gen_tokens, arrival_ms=None,
                         deadline_ms=t + args.deadline_ms,
                         extras=extras())
        completed = sched.run()
    else:
        arrivals = [(i * args.arrival_gap_ms,
                     rng.integers(0, cfg.vocab, args.prompt_len),
                     args.gen_tokens, extras()) for i in range(args.requests)]
        completed = run_arrivals(sched, arrivals)
    if args.profile:
        jax.profiler.stop_trace()
        print(f"profile: wrote jax.profiler trace to {args.profile}")
    return completed


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg, model, params = build_model(args)
    if args.legacy or args.fail_step >= 0:
        return _legacy(args, model, params)
    srv = build_serving(args, model, params)
    stepper, sched, injector, tracer, server = (
        srv.stepper, srv.sched, srv.injector, srv.tracer, srv.server)
    completed = serve_requests(args, cfg, sched)
    mode = "sequential" if sched.executor is None else \
        ("batched+overlap" if sched.rcfg.overlap else "batched")
    print(f"completed {len(completed)}/{args.requests} requests "
          f"({mode}; shed {len(sched.shed)})")
    if completed:
        print("tokens (first request):", completed[0].tokens)
    if sched.executor is not None:
        vstep = sched.executor.vstep
        print(f"executor: {vstep.n_dispatches} round dispatches "
              f"({vstep.n_fused} fused), {vstep.n_traces} trace(s)")
        if sched.executor.perf is not None \
                and sched.executor.perf.n_observed:
            s = sched.executor.perf.summary()
            share = (f"{s['roofline_utilization']:.4f} "
                     f"({s['dominant']}-bound)"
                     if "roofline_utilization" in s else "not measured")
            print(f"perf: {s['model_flops'] / 1e6:.2f} MFLOP useful/round "
                  f"({s['coded_overhead_frac']:.3f} coded overhead, "
                  f"{s['parity_device_equiv']:.3f} parity device-equiv), "
                  f"{s['achieved_flops_per_s'] / 1e9:.2f} GFLOP/s achieved, "
                  f"{s['hbm_gbs']:.2f} GB/s, roofline utilization {share}")
    if injector is not None:
        c = sched.metrics.counters
        print(f"chaos: {c['faults_injected']} injected events, "
              f"{c['erasures_recovered']} recovered in-step, "
              f"{c['beyond_budget_failures']} beyond budget")
    if args.adapt_r and sched.metrics.plan_log:
        series = [(p["t_ms"], p["r"]) for p in sched.metrics.plan_log]
        print(f"planner: r series {series} "
              f"(replans: {sched.metrics.counters['replans']})")
    if args.slo_report and sched.spans is not None:
        from repro.obs.slo import decompositions, render_report
        print("--- slo report " + "-" * 49)
        print(render_report(decompositions(sched.spans)))
        print("-" * 64)
    if args.trace:
        trace = write_chrome_trace(
            args.trace, tracer, sched.shardlog, now_ms=sched.clock.now(),
            meta={"arch": args.arch, "seed": args.seed,
                  "chaos": args.chaos or "", "adapt_r": args.adapt_r},
            spans=sched.spans)
        stats = validate_chrome_trace(
            trace, require_span_closure=sched.spans is not None
            and len(sched.spans.done) > 0)
        print(f"trace: wrote {args.trace} ({stats['n_events']} events on "
              f"{stats['n_tracks']} tracks; "
              f"{stats['n_injected_erasures']} injected erasures, all "
              f"linked to a resolution; {stats['n_span_trees']} request "
              f"span trees closed and gap-accounted)")
    if server is not None:
        server.stop()
    print(sched.metrics.to_json())
    if args.coded:
        print("straggler model (first-T-of-T+r):",
              stepper.straggler_latency(StragglerModel(), n_trials=5000))


if __name__ == "__main__":
    main()

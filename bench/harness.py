"""One run of one cell: build, warm, drive, measure, compare, report."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time

from bench import check, driver, traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: share of the window the traced run records, centred on its middle
TRACE_S = 6.0


# ------------------------------------------------------------- the spec --

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def metrics_for(spec: dict, cell: dict, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metric(name: str, run):
    """``bench/metrics/<name>.py``'s ``read(run)``: a number, or None
    when the run holds nothing for it to read."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def require_chips(n: int):
    """The first ``n`` TPU devices, or None (with the reason on stderr)
    when JAX finds no TPU or too few."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"bench: no TPU: JAX found only {devs[0].platform} "
              f"devices", file=sys.stderr)
        return None
    if len(devs) < n:
        print(f"bench: the cell needs {n} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:n]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed directory of the checkout, for
    every program however quick to compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def note(label: str, **fields):
    print(f"bench {label}: {json.dumps(fields, default=float)}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ the run --

@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    kind: str
    seconds: float
    stop_ms: float
    records: list
    rounds: list
    erase_ms: float | None
    setup: dict
    setup_s: float
    hf: dict
    serving: dict
    model: object            # bench.models.<model> module
    peaks: dict | None
    trace: object = None     # bench.trace.Summary of the traced window


def _build(cfg: dict, seed: int, model_mod):
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    from repro.models import TPCtx, build
    from repro.serve import ModelStepper
    hf, srv = cfg["hf"], cfg["serving"]
    code = srv["code"]
    arch = ArchConfig(name=cfg["name"], **model_mod.arch_fields(hf),
                      code_r=code["r"], code_layout=code["layout"])
    model = build(arch, TPCtx(tp=code["T"], mode="coded", code_r=code["r"],
                              code_layout=code["layout"], moe_capacity=0))
    dtype = jnp.dtype(srv["dtype"])
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model_mod.to_program(
        model_mod.make_weights(hf, k, dtype), hf, code["T"]))(
        model_mod.root_key(seed))
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    stepper = ModelStepper(model, params, max_len=srv["max_len"],
                           cache_dtype=jnp.dtype(srv["cache_dtype"]))
    del params
    jax.block_until_ready(stepper.params)
    t2 = time.perf_counter()
    return stepper, {"weights_s": t1 - t0, "encode_s": t2 - t1}


def _scheduler(stepper, cfg: dict, mix: dict, seconds: float, traced: bool):
    from repro.runtime import (ContinuousBatchingScheduler, RuntimeConfig,
                               ShardHealthController, erasure)
    events = []
    erase_ms = None
    if "erasure" in mix:
        erase_ms = seconds * 1e3 * float(mix["erasure"]["at_fraction"])
        events = [erasure(erase_ms, int(mix["erasure"]["shard"]))]
    health = ShardHealthController(stepper.n_shards, stepper.erasure_budget,
                                   events=events)
    clock = driver.BenchClock()
    rcfg = RuntimeConfig(n_slots=cfg["serving"]["n_slots"], profile=traced)
    sched = ContinuousBatchingScheduler(stepper, rcfg, clock=clock,
                                        health=health)
    return sched, clock, erase_ms


def _warm(sched, mix: dict, seed: int, vocab: int):
    """Compile and run every shape the window will use: one prefill per
    prompt length of the mix, and the decode round."""
    import jax
    ex = sched.executor
    full = sched.stepper.full_mask()
    for j, n in enumerate(traffic.distinct_prompt_lengths(mix)):
        ex.admit(j % ex.n_slots, traffic.prompt_tokens(
            seed, 10 ** 9 + j, n, vocab), full)
    for _ in range(3):
        ex.step_round(full)
    ex.drop_pending()
    jax.block_until_ready(ex.state)
    ex.evict_all()


class _Profiler:
    """Starts and stops the profiler from the loop, between steps."""

    def __init__(self, a_ms: float, b_ms: float, out_dir: str):
        self.a, self.b, self.dir = a_ms, b_ms, out_dir
        self.on = self.done = False

    def tick(self, now_ms: float):
        import jax
        if not self.on and not self.done and now_ms >= self.a:
            jax.profiler.start_trace(self.dir)
            self.on = True
        elif self.on and now_ms >= self.b:
            self.stop()

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def _memory(devices) -> dict:
    """The fullest chip's allocator readings that say how near its limit
    the program runs."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_free_block_bytes", "largest_alloc_size", "num_allocs")
    stats = [d.memory_stats() or {} for d in devices]
    full = max(stats, key=lambda m: m.get("bytes_in_use", 0))
    return {k: full[k] for k in keys if k in full}


def _kernel_counters() -> dict:
    """Linux counters that a stall of the whole host would move: CPU
    ticks stolen by the hypervisor, direct reclaim and compaction of
    memory, major page faults. Empty where they cannot be read."""
    out = {}
    try:
        with open("/proc/stat") as f:
            out["steal_ticks"] = int(f.readline().split()[8])
        with open("/proc/vmstat") as f:
            for line in f:
                k, v = line.split()
                if k in ("compact_stall", "pgmajfault", "allocstall_normal",
                         "allocstall_movable"):
                    out[k] = int(v)
    except (OSError, IndexError, ValueError):
        pass
    return out


class _GcWatch:
    """Python's collections and their pauses while it is attached."""

    def __init__(self):
        self.n = [0, 0, 0]
        self.pause_s = self.longest_s = 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.pause_s += dt
            self.longest_s = max(self.longest_s, dt)
            self.n[info["generation"]] += 1
            self._t = None


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _note_rounds(sess, seconds: float):
    """Round periods and tokens in each third of the window: a run that
    is slow throughout reads a longer median period in every third; one
    that stalls reads a long period (``slow_excess_s``, and where the
    longest ended) in some."""
    from bench import stats
    stamps = [t for t, _ in sess.rounds]
    end = seconds * 1e3
    thirds = []
    for i in range(3):
        a, b = end * i / 3, end * (i + 1) / 3
        pairs = [(y - x, y) for x, y in zip(stamps, stamps[1:])
                 if a <= y < b]
        if not pairs:
            return
        per = sorted(p for p, _ in pairs)
        med = per[len(per) // 2]
        longest, ends = max(pairs)
        thirds.append({
            "tokens": stats.tokens_in(sess.records.values(), a, b),
            "period_ms_p50": med, "period_ms_max": longest,
            "period_max_ends_ms": ends,
            "slow_excess_s": sum(p - med for p in per
                                 if p > 1.5 * med) / 1e3})
    note("rounds", thirds=thirds)


def run_cell(spec: dict, cell: dict, seed: int, seconds: float,
             traced: bool, *, t_start: float, devices, config: dict = None,
             mix: dict = None, control: bool = False, patch=None,
             root: str = ROOT) -> dict:
    """One run. ``config``/``mix`` replace the files named by the cell
    (tests and tools), ``patch(sched)`` alters the program under test
    after it is built (``bench.faults``), ``control`` also reads the
    int8 control and judges it by the same rows and limits (tools and
    tests: ``result["control"]["correct"]``), ``root`` holds the
    compile cache."""
    import jax

    from bench import costs
    setup = {"start_s": time.perf_counter() - t_start}
    enable_compile_cache(root)
    cfg = config or load_config(cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    model_mod = importlib.import_module(f"bench.models.{cfg['model']}")
    ref_mod = importlib.import_module(f"bench.reference.{cfg['model']}")
    hf, srv = cfg["hf"], cfg["serving"]
    vocab = hf["vocab_size"]
    dev = devices[0]
    pk = costs.peaks(dev.device_kind) if dev.platform == "tpu" else None

    stepper, times = _build(cfg, seed, model_mod)
    setup.update(times)
    t0 = time.perf_counter()
    sched, clock, erase_ms = _scheduler(stepper, cfg, mix, seconds, traced)
    if patch is not None:
        patch(sched)
    setup["scheduler_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    _warm(sched, mix, seed, vocab)
    setup["warmup_s"] = time.perf_counter() - t0
    note("memory", after="warm-up", **_memory(devices))
    sess = driver.Session(sched, clock, traced=traced)
    prompts = lambda p: traffic.prompt_tokens(  # noqa: E731
        seed, p.idx, p.prompt_len, vocab)

    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if traced else None
    prof = None
    if traced:
        a = max(0.0, (seconds - TRACE_S) / 2) * 1e3
        prof = _Profiler(a, a + min(TRACE_S, seconds) * 1e3, tmp.name)
    tick = prof.tick if prof else None
    t_window = [None]
    watch = _GcWatch()
    cpu0 = [None]
    kernel0 = {}
    wall0 = [None]

    def on_tick(now):
        if t_window[0] is None:
            t_window[0] = time.perf_counter()
            wall0[0] = time.time()
            cpu0[0] = time.process_time()
            kernel0.update(_kernel_counters())
            gc.callbacks.append(watch)
        if tick:
            tick(now)

    if mix["kind"] == "open":
        planned = traffic.plan(mix, seconds + float(mix["drain_s"]))
        stop_ms = driver.run_open(sess, planned, prompts, seconds,
                                  float(mix["drain_s"]), on_tick=on_tick)
    else:
        counter = iter(range(10 ** 9))

        def next_request():
            p = traffic.backlog_request(mix, next(counter))
            return p, prompts(p)

        t0 = time.perf_counter()
        stop_ms = driver.run_backlog(sess, next_request, seconds,
                                     on_tick=on_tick)
        setup["fill_s"] = t_window[0] - t0
    t_end = time.perf_counter()
    cpu_s = time.process_time() - cpu0[0]
    kernel = {k: v - kernel0[k] for k, v in _kernel_counters().items()
              if k in kernel0}
    gc.callbacks.remove(watch)
    if prof:
        prof.stop()
    note("memory", after="window", **_memory(devices))
    sched.executor.drop_pending()
    jax.block_until_ready(sched.executor.state)
    setup_s = t_window[0] - t_start
    peak = _peak_bytes(devices)
    note("setup", setup_s=setup_s, **setup)
    if mix["kind"] == "open":
        late = sorted(sess.lateness_ms)
        note("generator", submitted=len(late),
             lateness_ms_p50=late[len(late) // 2] if late else 0.0,
             lateness_ms_max=late[-1] if late else 0.0)
    window_s = t_end - t_window[0]
    note("host", window_s=window_s, in_round_s=sess.in_round_s,
         in_admit_s=sess.in_admit_s,
         loop_s=window_s - sess.in_round_s - sess.in_admit_s,
         cpu_s=cpu_s, gc_collections=watch.n, gc_pause_s=watch.pause_s,
         gc_longest_s=watch.longest_s, loadavg=os.getloadavg(),
         kernel=kernel, window_start_unix=wall0[0])
    _note_rounds(sess, seconds)

    records = sorted(sess.records.values(), key=lambda r: r.planned.idx)
    run = Run(kind=mix["kind"], seconds=seconds, stop_ms=stop_ms,
              records=records, rounds=list(sess.rounds), erase_ms=erase_ms,
              setup=setup, setup_s=setup_s, hf=hf, serving=srv,
              model=model_mod, peaks=pk)
    # the program's state goes before the reference runs
    del sess, sched, stepper, prompts
    gc.collect()

    if traced:
        from bench import trace as trace_mod
        t0 = time.perf_counter()
        run.trace = trace_mod.reduce_dir(tmp.name)
        tmp.cleanup()
        note("trace", seconds=time.perf_counter() - t0,
             ops=len(run.trace.ops) if run.trace else 0)

    t0 = time.perf_counter()
    if mix["kind"] == "open":
        from bench.stats import failed, window_requests
        n_failed = len(failed(records, seconds))
        attempted = len(window_requests(records, seconds))
    else:
        n_failed = 0
        attempted = sum(1 for r in records if r.token_ms)
    picked = check.sample(records, seed, int(mix["check"]["sample_requests"]),
                          erase_ms)
    weights = jax.jit(lambda k: model_mod.make_weights(
        hf, k, jax.numpy.dtype(srv["dtype"])))(model_mod.root_key(seed))
    reading = check.compare(ref_mod, hf, weights, picked, srv["max_len"],
                            control=control)
    del weights
    min_tokens = int(cfg["correct"]["min_tokens"])
    rows = check.compared(reading, cfg["correct"], n_failed, min_tokens)
    note("check", seconds=time.perf_counter() - t0, **reading)

    metrics = {}
    for m in metrics_for(spec, cell, traced):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": all(r["ok"] for r in rows.values()),
              "attempted": attempted, "failed": n_failed,
              "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    if control:
        crows = check.compared(reading["control"], cfg["correct"], n_failed,
                               min_tokens)
        result["control"] = {"correct": all(r["ok"] for r in crows.values()),
                             "compared": _limits(crows)}
    result["compared"] = _limits(rows)
    return result


def _limits(rows: dict) -> dict:
    return {k: {"value": r["value"], "limit": r["limit"]}
            for k, r in rows.items()}


def emit(result: dict):
    """The compared numbers as the last lines on stderr (a control's
    before the program's), then the result as the last line on
    stdout."""
    for k, r in result.get("control", {}).get("compared", {}).items():
        print(f"bench control compared {k}: {r['value']} "
              f"(limit {r['limit']})", file=sys.stderr, flush=True)
    for k, r in result["compared"].items():
        print(f"bench compared {k}: {r['value']} (limit {r['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

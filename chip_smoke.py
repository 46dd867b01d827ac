"""Chip smoke test: the coded serving path on a TPU, end to end.

  python chip_smoke.py               # one TPU chip (the default)
  python chip_smoke.py --four-chips  # the coded forward over a 4-chip mesh
  python chip_smoke.py --rehearse    # the same phases on the CPU, smoke width

One chip: h2o-danube-1.8b at its published widths, coded T=4, r=2
(folded parity), random bf16 params from a fixed seed, served through the
launcher's own setup (``repro.launch.serve``): scheduler -> slot-pool
executor, 4 slots, 6 requests of 128 prompt tokens and 16 generated
tokens, one in-budget shard erasure mid-stream. Checks that every request
completes, the erasure is recovered in-step with nothing requeued, the
full-Pallas round ran and its compiled HLO holds the Mosaic kernels, and
that one decode step agrees across the fused round (with the erasure),
the reference coded round (with and without it) and the plain model.

Four chips: a (data=1, model=4) mesh places coded shard i on model-rank i
(``repro.dist.param_shardings``); the jitted coded forward with rank 1
masked dead must match the one-chip forward of the same params.

Every phase prints a labelled line; any failed check makes the script
exit non-zero (after the remaining phases report). The last line is one
JSON object: {"ok": true, "device": {...}}. Without a
TPU the script exits non-zero before any phase (``--rehearse`` runs the
phases on the CPU, with Pallas in interpret mode, and reports the CPU).
All phases run in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.ref import TOL  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "h2o-danube-1.8b"
SLOTS, REQUESTS, PROMPT, GEN = 4, 6, 128, 16
DEAD = 1                          # the shard erased mid-stream
KERNELS = ("cdc_coded_matmul_pallas", "cdc_fused_head_argmax_pallas")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def report(label: str, **fields):
    print(f"{label}: {json.dumps(fields, default=str)}", flush=True)


FAILED: list[str] = []


def check(name: str, ok: bool, **detail):
    """Report one check; a failure is remembered and the later phases
    still run and report, but the script then exits non-zero."""
    report(f"check {name}", result="pass" if ok else "FAIL", **detail)
    if not ok:
        FAILED.append(name)


def serve_args(rehearse: bool) -> argparse.Namespace:
    argv = ["--arch", ARCH, "--coded", "--tp", "4", "--dtype", "bfloat16",
            "--batch", str(SLOTS), "--requests", str(REQUESTS),
            "--prompt-len", str(PROMPT), "--gen-tokens", str(GEN),
            "--fail-time-ms", "8", "--fail-shard", str(DEAD)]
    if rehearse:
        # smoke width; the CPU needs the fused round forced (interpret)
        argv += ["--smoke", "--fused"]
    return serve.build_parser().parse_args(argv)


def devices_or_exit(rehearse: bool, n: int) -> list:
    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit(f"chip_smoke: --rehearse is the CPU rehearsal; JAX "
                     f"found {platform}")
    elif platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found only {platform} devices "
                 f"({len(devs)}). Run on a TPU host, or --rehearse for the "
                 f"CPU rehearsal at smoke width.")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} devices, JAX found {len(devs)}")
    report("device", platform=platform, kind=devs[0].device_kind,
           count=len(devs), using=n, jax=jax.__version__)
    return devs[:n]


def max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def allclose(a, b, tol) -> bool:
    return bool(np.allclose(np.asarray(a, np.float32),
                            np.asarray(b, np.float32), **tol))


def memory_report(devs, phase: str):
    """Device memory after ``phase``; the peak is over the whole process."""
    for d in devs:
        stats = d.memory_stats() or {}
        report("memory", phase=phase, device=d.id, **{
            k: stats.get(k, "not reported")
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})


def compile_round(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def kernels_in_hlo(hlo: str) -> dict:
    """Per fused kernel: does a Mosaic custom call of that name exist?"""
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {k: any(k in ln for ln in calls) for k in KERNELS}


def one_chip(rehearse: bool):
    devs = devices_or_exit(rehearse, 1)
    tol = TOL["bfloat16"]
    args = serve_args(rehearse)

    # -------------------------------------------------------- build ----
    t0 = time.perf_counter()
    cfg, model, params = serve.build_model(args)
    srv = serve.build_serving(args, model, params)
    del params                    # the stepper holds its own references
    st, sched = srv.stepper, srv.sched
    jax.block_until_ready(st.params)
    report("model", arch=cfg.name, d_model=cfg.d_model,
           n_layers=cfg.n_layers, d_ff=cfg.d_ff, vocab=cfg.vocab,
           T=st.n_shards, r=model.ctx.code_r, layout=model.ctx.code_layout,
           dtype=args.dtype,
           coded_params=sum(x.size for x in jax.tree.leaves(st.params)),
           build_s=time.perf_counter() - t0)
    memory_report(devs, "build")

    # ------------------------------------------------------ compile ----
    ex = sched.executor
    vs = ex.vstep
    check("fused round selected", vs.use_fused, use_fused="auto"
          if not rehearse else "forced (interpret)")
    full = st._mask(st.full_mask())
    dead = st._mask(np.arange(st.n_shards) != DEAD)
    w_sh, pw = vs._head_shards()
    fused_c, fused_s = compile_round(vs._round_fused, st.params, ex.state,
                                     ex.last_toks, full, w_sh, pw)
    ref_c, ref_s = compile_round(vs._round, st.params, ex.state,
                                 ex.last_toks, full)
    report("compile", fused_round_s=fused_s, reference_round_s=ref_s,
           cache_dir=jax.config.jax_compilation_cache_dir)
    memory_report(devs, "compile")
    found = kernels_in_hlo(fused_c.as_text())
    if rehearse:
        report("hlo", note="interpret mode inlines the kernels; Mosaic "
                           "custom calls not checked off the TPU")
    else:
        check("fused round HLO holds the Mosaic kernels", all(found.values()),
              **found)

    # -------------------------------------------------------- serve ----
    t0 = time.perf_counter()
    completed = serve.serve_requests(args, cfg, sched)
    serve_s = time.perf_counter() - t0
    c = sched.metrics.counters
    check("every request completes",
          len(completed) == REQUESTS
          and all(len(r.tokens) == GEN for r in completed),
          completed=len(completed), requests=REQUESTS)
    check("erasure recovered in-step", c["erasures_recovered"] >= 1,
          erasures_recovered=c["erasures_recovered"], dead_shard=DEAD)
    check("nothing requeued", c["requests_requeued"] == 0,
          requests_requeued=c["requests_requeued"])
    check("fused rounds dispatched", vs.n_fused > 0, fused=vs.n_fused,
          rounds=vs.n_dispatches, traces=vs.n_traces)
    rounds = sched.metrics.round_ms
    report("serve", wall_s=serve_s, rounds=len(rounds),
           round_ms_p50=rounds.percentile(50),
           round_ms_p90=rounds.percentile(90),
           tokens=c["tokens_generated"])
    memory_report(devs, "serve")

    # ---------------------------------------------------- agreement ----
    # four fresh prompts through the executor's own admission (prefill),
    # then one decode step through every variant on the same state
    rng = np.random.default_rng(2)
    for slot in range(SLOTS):
        ex.admit(slot, rng.integers(0, cfg.vocab, PROMPT), st.full_mask())
    state, toks = ex.state, ex.last_toks
    _, tok_f, val_f = fused_c(st.params, state, toks, dead, w_sh, pw)
    _, _, logits_dead = ref_c(st.params, state, toks, dead)
    _, _, logits_ok = ref_c(st.params, state, toks, full)
    plain = dataclasses.replace(
        st.model, ctx=dataclasses.replace(st.model.ctx, mode="plain"))
    logits_plain, _ = jax.jit(lambda p, s, t: plain.decode(p, s, t))(
        st.params, state, toks)
    ld = np.asarray(logits_dead, np.float32)[:, -1]          # [slots, V]
    lo = np.asarray(logits_ok, np.float32)[:, -1]
    lp = np.asarray(logits_plain, np.float32)[:, -1]
    tok_f = np.asarray(tok_f)[:, 0]
    val_f = np.asarray(val_f, np.float32)
    report("tolerance", dtype="bfloat16", **tol)
    check("reference round: erasure vs fault-free logits",
          allclose(ld, lo, tol), max_abs_err=max_err(ld, lo))
    check("reference round vs plain model logits",
          allclose(lo, lp, tol), max_abs_err=max_err(lo, lp))
    check("fused round max logit vs reference (erasure)",
          allclose(val_f, ld.max(-1), tol),
          max_abs_err=max_err(val_f, ld.max(-1)))
    picked = ld[np.arange(SLOTS), tok_f]
    check("fused round token is a reference argmax within tolerance",
          allclose(picked, ld.max(-1), tol),
          fused_tokens=tok_f.tolist(),
          reference_tokens=ld.argmax(-1).tolist(),
          max_abs_gap=max_err(picked, ld.max(-1)))
    memory_report(devs, "agreement")
    return devs


def coded_weight_bytes(params) -> dict:
    """Bytes of the coded GEMMs' weights and parity on each device."""
    per_dev: dict = {}

    def walk(node):
        if isinstance(node, dict):
            if "w" in node and "cdc" in node:
                for leaf in (node["w"], node["cdc"]):
                    for sh in leaf.addressable_shards:
                        per_dev[sh.device.id] = per_dev.get(
                            sh.device.id, 0) + sh.data.nbytes
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return per_dev


def four_chips(rehearse: bool):
    from jax.sharding import NamedSharding

    from repro.dist import batch_spec, param_shardings
    from repro.launch.mesh import make_mesh

    devs = devices_or_exit(rehearse, 4)
    tol = TOL["bfloat16"]
    args = serve_args(rehearse)
    cfg, model, params = serve.build_model(args)
    T = model.ctx.tp
    batch = model.dummy_batch(jax.random.PRNGKey(1), SLOTS, PROMPT)
    full = jnp.ones((T,), bool)
    dead = jnp.arange(T) != DEAD
    report("model", arch=cfg.name, d_model=cfg.d_model,
           n_layers=cfg.n_layers, T=T, r=model.ctx.code_r,
           layout=model.ctx.code_layout, dtype=args.dtype,
           batch=[SLOTS, PROMPT])

    # one chip: the same params, unsharded, on device 0
    fwd1 = jax.jit(lambda p, b, v: model.forward(p, b, v))
    ref_dead = np.asarray(fwd1(params, batch, dead), np.float32)
    ref_ok = np.asarray(fwd1(params, batch, full), np.float32)

    mesh = make_mesh((1, T), ("data", "model"), devices=devs)
    m4 = dataclasses.replace(model,
                             ctx=dataclasses.replace(model.ctx, mesh=mesh))
    params_sh = jax.device_put(params, param_shardings(params, mesh))
    batch_sh = jax.device_put(
        batch, {"tokens": NamedSharding(mesh, batch_spec(mesh))})
    compiled, compile_s = compile_round(
        jax.jit(lambda p, b, v: m4.forward(p, b, v)), params_sh, batch_sh,
        dead)
    hlo = compiled.as_text()
    colls = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
             for k in COLLECTIVES}
    report("compile", sharded_forward_s=compile_s, collectives=colls)
    check("sharded forward HLO holds collectives",
          colls["all-gather"] + colls["all-reduce"] > 0, **colls)
    shares = coded_weight_bytes(params_sh)
    total = sum(shares.values())
    report("coded weight share", **{f"device_{d}": shares[d] / total
                                    for d in sorted(shares)})
    check("coded weights spread over the four chips",
          len(shares) == T and max(shares.values()) < 0.3 * total,
          bytes={str(d): b for d, b in sorted(shares.items())})
    got = np.asarray(compiled(params_sh, batch_sh, dead), np.float32)
    report("tolerance", dtype="bfloat16", **tol)
    check("sharded forward, rank 1 dead, vs one-chip forward",
          allclose(got, ref_dead, tol), max_abs_err=max_err(got, ref_dead))
    # the two bf16 differences compound here (erasure recovery and the
    # mesh's reduction order), so this one is reported, not checked
    report("sharded forward, rank 1 dead, vs one-chip fault-free",
           max_abs_err=max_err(got, ref_ok), within_tolerance=allclose(
               got, ref_ok, tol))
    memory_report(devs, "four-chip forward")
    return devs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the coded forward sharded over 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on the CPU at smoke width "
                         "(Pallas interpret mode)")
    args = ap.parse_args()
    enable_compile_cache()
    devs = four_chips(args.rehearse) if args.four_chips \
        else one_chip(args.rehearse)
    if FAILED:
        sys.exit(f"chip_smoke: {len(FAILED)} check(s) failed: "
                 + "; ".join(FAILED))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

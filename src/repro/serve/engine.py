"""Serving stepper: the model-facing half of the coded serving stack.

This module used to be a monolithic synchronous engine; the scheduling,
failure-policy, and telemetry concerns now live in ``repro.runtime``. What
remains here is the *stepper* — the minimal stateful object the runtime
drives:

  * ``ModelStepper``: owns the CDC-encoded params and the jitted decode
    step; exposes prefill / decode-one-token / re-encode. It never looks
    at clocks, queues, or failure policy — the runtime feeds it the
    CURRENT validity mask each call, so a shard loss mid-request is
    recovered inside the same XLA program (close-to-zero recovery: no
    re-dispatch, no weight reload, no recompute — paper §5.2).
  * ``ServingEngine``: the legacy one-batch-at-a-time facade, kept for
    direct scripted use and the original integration tests; it is now a
    thin wrapper over ``ModelStepper``.

Straggler mitigation (§6.2) stays here as a latency *model*: a synchronous
TPU mesh can't skip laggards inside a step, so the stepper exposes the
paper's first-T-of-(T+r) order-statistic distribution for the pod/DCN
boundary, simulated with the measured per-shard latencies (core.failure).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.failure import StragglerModel, request_latency
from repro.models.zoo import Model
# tracer module only (no package init): keeps serve <-> runtime acyclic
from repro.obs.tracer import NULL_RECORDER


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    batch: int = 8
    cache_dtype: Any = jnp.float32
    greedy: bool = True


class ModelStepper:
    """Thin model stepper the runtime drives.

    Holds encoded params + one jitted decode function; all slot states are
    caller-owned pytrees, so the runtime can keep any number of independent
    decode slots (continuous batching) over a single compiled step.
    """

    def __init__(self, model: Model, params, max_len: int,
                 cache_dtype: Any = jnp.float32, tracer=None):
        self.model = model
        self.max_len = int(max_len)
        self.cache_dtype = cache_dtype
        # flight recorder (repro.obs); the scheduler re-binds its own so
        # code-geometry changes land in the same event stream
        self.tracer = tracer if tracer is not None else NULL_RECORDER
        # parity is always recomputed from the "w" leaves, so re-encodes
        # start from self.params: keeping the caller's tree as well would
        # hold a second copy of every parity weight on the device
        self.params = model.encode_offline(params)
        self.coded = bool(model.ctx.coded)
        self.n_shards = max(int(model.ctx.tp), 1)
        spec = model.ctx.spec
        self.erasure_budget = int(spec.max_device_failures) if spec else 0
        # reads self.model at trace time so set_code_r's swapped context is
        # picked up: an r change alters the parity-leaf shapes, which is
        # exactly what keys a fresh jit trace
        self._decode = jax.jit(
            lambda p, st, tok, valid: self.model.decode(p, st, tok, valid))
        # span emission points (obs.spans): MEASURED dispatch-side wall
        # cost of the last prefill / parity re-encode. Wall-clock only —
        # quarantined in span wall_args, never in the simulated timeline.
        self.last_prefill_wall_ms: float = 0.0
        self.last_reencode_wall_ms: float = 0.0

    # ------------------------------------------------------------ coding ----
    def reencode(self):
        """Offline parity re-encode (paper §5.1): run after a healed shard
        rejoins or a standby replica is swapped in."""
        t0 = time.perf_counter()
        self.params = self.model.encode_offline(self.params)
        self.last_reencode_wall_ms = (time.perf_counter() - t0) * 1e3

    def set_code_r(self, code_r: int) -> bool:
        """Re-size the parity budget (adaptive redundancy): rebuild the
        coded context and re-encode parity offline — the same heal +
        re-encode path a replica swap takes, plus a round retrace since
        the parity-weight shapes change. Decode slot states (KV caches)
        are r-independent, so in-flight requests carry straight on.
        Returns True iff the geometry changed."""
        code_r = int(code_r)
        if code_r < 0:
            raise ValueError(f"code_r must be >= 0, got {code_r}")
        if not self.coded or code_r == int(self.model.ctx.code_r):
            return False
        r_old = int(self.model.ctx.code_r)
        ctx = dataclasses.replace(self.model.ctx, code_r=code_r)
        self.model = dataclasses.replace(self.model, ctx=ctx)
        self.params = self.model.encode_offline(self.params)
        spec = ctx.spec
        self.erasure_budget = int(spec.max_device_failures) if spec else 0
        if self.tracer.enabled:
            self.tracer.emit("code.resize", track="rounds", r_old=r_old,
                             r_new=code_r, budget=self.erasure_budget)
        return True

    def full_mask(self) -> np.ndarray:
        return np.ones(self.n_shards, bool)

    def _mask(self, valid) -> jax.Array | None:
        if valid is None:
            return None
        return jnp.asarray(np.asarray(valid, bool))

    # ---------------------------------------------------------- stepping ----
    def prefill(self, batch: dict, valid=None,
                per_row: bool = False) -> tuple[jax.Array, Any]:
        """Run the prompt through the decode path, filling a fresh slot
        state. Returns (last-position logits [b, 1, V], state).

        per_row=True builds the slot-batched cache layout (per-row position
        vectors) so the state can be written into a stacked executor batch.
        """
        t0 = time.perf_counter()
        v = self._mask(valid) if self.coded else None
        b = batch["tokens"].shape[0]
        state = self.model.init_decode(self.params, batch, b, self.max_len,
                                       self.cache_dtype, valid=v,
                                       per_row=per_row)
        logits, state = self._decode(self.params, state, batch["tokens"], v)
        self.last_prefill_wall_ms = (time.perf_counter() - t0) * 1e3
        return logits[:, -1:], state

    def decode_one(self, state, tok: jax.Array, valid=None
                   ) -> tuple[jax.Array, Any]:
        """One decode step: tok [b, 1] -> (logits [b, 1, V], new state)."""
        v = self._mask(valid) if self.coded else None
        return self._decode(self.params, state, tok, v)

    @staticmethod
    def greedy(logits: jax.Array) -> jax.Array:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # ------------------------------------------------- straggler model ----
    def straggler_latency(self, straggler: StragglerModel,
                          n_trials: int = 10000, seed: int = 0) -> dict:
        """First-T-of-(T+r) request-latency distribution across the coded
        shard set (paper Fig. 14/15): pod-level dispatch only needs T of
        T+r shard responses."""
        T = self.n_shards
        r = int(self.model.ctx.code_r if self.coded else 0)
        rng = np.random.default_rng(seed)
        times = straggler.sample(rng, (n_trials, T + r))
        coded = request_latency(times, T)
        uncoded = request_latency(times[:, :T], T)
        return {
            "mean_coded_ms": float(coded.mean()),
            "mean_uncoded_ms": float(uncoded.mean()),
            "p99_coded_ms": float(np.percentile(coded, 99)),
            "p99_uncoded_ms": float(np.percentile(uncoded, 99)),
        }


class ServingEngine:
    """Legacy synchronous facade over ``ModelStepper``.

    One batch at a time, caller-managed failure injection. New code should
    use ``repro.runtime.ContinuousBatchingScheduler``, which drives the
    same stepper under sustained load with a shard-health controller.

    ``generate`` DELEGATES to the batched ``SlotPoolExecutor`` (every
    batch row becomes a slot, rounds are one dispatch) so this deprecated
    entry point exercises the exact same hot path as the runtime and
    cannot silently diverge from it — for every zoo family, enc-dec and
    xLSTM included. ``_generate_sequential`` remains as the
    differential-test oracle.
    """

    def __init__(self, model: Model, params, scfg: ServeConfig):
        self.model = model
        self.scfg = scfg
        self.stepper = ModelStepper(model, params, scfg.max_len,
                                    scfg.cache_dtype)
        self.valid = jnp.ones(self.stepper.n_shards, bool)
        self.metrics = {"requests": 0, "erasures_recovered": 0,
                        "requeued": 0}
        self._executors: dict[int, Any] = {}   # batch size -> warm executor

    @property
    def params(self):
        return self.stepper.params

    # -------------------------------------------------------- failures ----
    def inject_failure(self, shard: int):
        """Mark a TP shard dead. Subsequent steps recover via parity."""
        self.valid = self.valid.at[shard].set(False)
        self.metrics["erasures_recovered"] += 1

    def heal(self, shard: int | None = None):
        if shard is None:
            self.valid = jnp.ones_like(self.valid)
        else:
            self.valid = self.valid.at[shard].set(True)
        self.stepper.reencode()

    # ---------------------------------------------------------- serving ----
    def prefill(self, batch: dict) -> Any:
        logits, state = self.stepper.prefill(batch, self.valid)
        return logits, state

    def generate(self, batch: dict, n_tokens: int,
                 fail_at: dict[int, int] | None = None) -> np.ndarray:
        """Greedy generation; ``fail_at`` maps step -> shard to kill mid-
        request (the paper's Case Study II: performance unchanged)."""
        # deferred import: repro.runtime imports this module for the stepper
        from repro.runtime.executor import SlotPoolExecutor
        tokens = np.asarray(batch["tokens"])
        extras_all = {k: np.asarray(v) for k, v in batch.items()
                      if k != "tokens"}
        b = tokens.shape[0]
        ex = self._executors.get(b)
        if ex is None:
            ex = SlotPoolExecutor(self.stepper, n_slots=b, overlap=False)
            self._executors[b] = ex
        else:
            # reuse the warm jit cache; admission overwrites every row
            ex.drop_pending()
            ex.evict_all()
        out = np.zeros((b, n_tokens), np.int64)
        for i in range(b):
            extras = {k: v[i] for k, v in extras_all.items()} or None
            out[i, 0] = ex.admit(i, tokens[i], self.valid, tag=i,
                                 extras=extras)
        for t in range(n_tokens - 1):
            if fail_at and t in fail_at:
                self.inject_failure(fail_at[t])
            for slot, _, tok in ex.step_round(self.valid):
                out[slot, t + 1] = tok
        self.metrics["requests"] += b
        return out

    def _generate_sequential(self, batch: dict, n_tokens: int,
                             fail_at: dict[int, int] | None) -> np.ndarray:
        """Sequential per-slot stepping — the differential-test oracle the
        batched path is pinned against (no longer a production path)."""
        logits, state = self.prefill(batch)
        tok = self.stepper.greedy(logits)
        out = [tok]
        for t in range(n_tokens - 1):
            if fail_at and t in fail_at:
                self.inject_failure(fail_at[t])
            logits, state = self.stepper.decode_one(state, tok, self.valid)
            tok = self.stepper.greedy(logits)
            out.append(tok)
        self.metrics["requests"] += batch["tokens"].shape[0]
        return np.concatenate([np.asarray(t) for t in out], axis=1)

    # ------------------------------------------------- straggler model ----
    def straggler_latency(self, straggler: StragglerModel,
                          n_trials: int = 10000, seed: int = 0) -> dict:
        return self.stepper.straggler_latency(straggler, n_trials, seed)

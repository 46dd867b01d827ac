"""One jitted, vectorised decode round over the stacked slot state.

The whole slot pool advances one token in a SINGLE device dispatch for
EVERY zoo family: per-row KV positions let transformer slots attend at
their own offsets, the enc-dec extras bank gives each whisper slot its
own cross-attention context, and xLSTM rows advance their positionless
block state independently. The health controller's validity mask is
broadcast into every coded GEMM of the round, so an in-budget erasure is
recovered in-step for all slots at once (the paper's close-to-zero
recovery, now a pool-level property).

Two compiled variants exist, both traced exactly once:

  * reference — the model's coded decode returning full last-position
    logits (what the equivalence and erasure-sweep tests pin down);
  * fused     — the FULL-Pallas round: the model body runs with
    ``ctx.fused_body=True`` so every in-body coded GEMM (attention QKV,
    FFN up/gate) goes through ``kernels.cdc_matmul`` — shard GEMMs +
    Eq. 12 parity decode + merge in ONE kernel, per-shard outputs never
    materialised in HBM — and the final norm feeds the Pallas fused
    coded-head kernel (``kernels.cdc_decode``): head GEMM + parity
    decode + greedy argmax, logits never hitting HBM either.
    Valid for <= 1 erased shard (the in-register Eq. 12 regime); rounds
    beyond that fall back to the reference path — ``round()`` counts the
    host mask BEFORE dispatch, so a 2+-erasure round (in budget only for
    the dedicated layout) always gets the reference MDS decode, never a
    silent wrong answer. Off TPU the kernels run in Pallas interpret
    mode; ``use_fused="auto"`` therefore enables them only where they
    compile natively.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.cdc_decode import pad_head_shards


def _fused_supported(stepper) -> bool:
    # arch-agnostic: every zoo family's decode exposes return_hidden and
    # ends in the same coded LM head, so the fused kernel only needs the
    # sum-parity generator row it consumes
    return (stepper.coded
            and bool(np.allclose(stepper.model.ctx.spec.code.generator[0],
                                 1.0)))


class VStep:
    """Owns the jitted round functions and their dispatch/trace counters.

    ``n_traces`` increments only when jit actually retraces — the
    executor tests assert it stays at one per variant while ``n_dispatches``
    grows with the rounds, i.e. the hot path is one compiled program.
    """

    def __init__(self, stepper, use_fused: bool | str = "auto"):
        self.stepper = stepper
        if use_fused == "auto":
            use_fused = (_fused_supported(stepper)
                         and jax.default_backend() == "tpu")
        self.use_fused = bool(use_fused) and _fused_supported(stepper)
        self.n_traces = 0
        self.n_dispatches = 0
        self.n_fused = 0        # of n_dispatches, full-Pallas rounds
        # which compiled program the LAST round() call dispatched — the
        # perf monitor attributes each harvested round to its variant
        self.last_variant = "reference"

        # closures read stepper.model at TRACE time: a planner-driven
        # set_code_r swaps the coded context, its new parity shapes key a
        # fresh trace, and that trace must see the new geometry
        def _round(params, state, toks, valid):
            self.n_traces += 1
            logits, new_state = stepper.model.decode(params, state, toks,
                                                     valid)
            last = logits[:, -1:]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return new_state, nxt, last

        self._round = jax.jit(_round)

        def _round_fused(params, state, toks, valid, w_shards, parity_w):
            self.n_traces += 1
            # fused-body context: every in-body coded GEMM of this trace
            # goes through the fused Pallas kernel (cdc_matmul). Built at
            # trace time from the CURRENT model so set_code_r retraces
            # with the new geometry, like the reference closure.
            model = stepper.model
            fm = dataclasses.replace(
                model, ctx=dataclasses.replace(model.ctx, fused_body=True))
            hidden, new_state = fm.decode(params, state, toks, valid,
                                          return_hidden=True)
            tok, val = ops.fused_head_argmax(
                hidden[:, -1, :].astype(jnp.float32), w_shards, parity_w,
                valid, vocab=stepper.model.cfg.vocab,
                shard_width=params["lm_head"]["w"].shape[1]
                // stepper.n_shards)
            return new_state, tok[:, None], val

        self._round_fused = jax.jit(_round_fused)
        self._head_cache: tuple[int, Any, Any] | None = None

    # ----------------------------------------------------------- fused ----
    def _head_shards(self):
        """[T, k, m_l] column shards + sum-parity weight of the LM head,
        zero-padded to whole lanes and cached per params object
        (refreshed by re-encode)."""
        params = self.stepper.params
        if self._head_cache is None or self._head_cache[0] != id(params):
            w = params["lm_head"]["w"]
            k, m = w.shape
            t = self.stepper.n_shards
            w_shards = jnp.moveaxis(w.reshape(k, t, m // t), 1, 0)
            # the sum parity in f32, like every parity weight (CodeSpec)
            parity_w = w_shards.astype(jnp.float32).sum(0)
            self._head_cache = (id(params),
                                *pad_head_shards(w_shards, parity_w))
        return self._head_cache[1], self._head_cache[2]

    # ----------------------------------------------------------- rounds ----
    def round(self, state, toks, valid) -> tuple[Any, jax.Array,
                                                 jax.Array | None]:
        """One decode round over the stacked state. valid: [T] bool host
        mask. Returns (new_state, next_toks [n,1], last_logits or None
        when the fused head skipped materialising them)."""
        st = self.stepper
        v = st._mask(valid) if st.coded else None
        self.n_dispatches += 1
        if self.use_fused and v is not None \
                and int(st.n_shards - np.asarray(valid).sum()) <= 1:
            self.last_variant = "fused"
            self.n_fused += 1
            w_shards, parity_w = self._head_shards()
            new_state, nxt, _ = self._round_fused(st.params, state, toks, v,
                                                  w_shards, parity_w)
            return new_state, nxt, None
        self.last_variant = "reference"
        return self._round(st.params, state, toks, v)

"""Pallas kernels for the CDC decode hot path.

``cdc_decode_pallas`` — the r=1 recovery combine (paper Eq. 12):
y_missing = parity - sum_{i valid} y_i, then scatter into the erased slot:
  out[i] = valid[i] ? y[i] : (parity - sum_j valid[j]*y[j])
This is the paper's "close-to-zero" recovery: one fused elementwise pass over
the gathered shard outputs — no recompute, no weight reload. Memory-bound:
reads (T+1) blocks, writes T. The general r>1 MDS decode solves a tiny system
and stays in plain JAX (repro.core.coding/coded_layer); this kernel is the
hot path that runs on EVERY request in coded serving.

Layout: shard outputs stacked [T, rows, m_l]; tiles (rows, bn) with the full
shard axis resident (T <= 64), validity mask as a [T] VMEM block.

``cdc_fused_head_argmax_pallas`` — the batched-executor decode step: coded
LM-head GEMM + Eq. 12 parity decode + greedy argmax in ONE kernel. Per
column tile it computes every shard's head output y_d = x @ W_d plus the
sum-parity output p = x @ W_cdc0, recovers an erased shard in-register, and
folds a running (max, argmax) over the merged vocabulary — the [B, vocab]
logits tensor is never materialised in HBM. Grid (vocab tiles, k tiles),
both sequential: the k axis accumulates (T+1) [b, bn] f32 tiles in VMEM,
the last k step decodes and updates the running argmax.

Erasure limit (ASYMMETRY with the reference path, by design): both kernels
here consume exactly ONE parity equation — the all-ones sum row (paper
Eq. 12) — so they recover at most ONE erased shard even when the code's
budget is larger (dedicated layout with r=2 tolerates 2). The reference
path (full logits + ``core.coding.decode_outputs`` MDS solve) covers the
full budget. ``executor.vstep.round`` counts the host mask BEFORE
dispatch and routes 2+-erasure rounds to the reference variant, and
``kernels.ops`` raises on host-concrete masks beyond the limit — an
in-budget multi-erasure round degrades to the slower exact path, never to
a silently wrong token. (The in-BODY fused kernels in ``cdc_matmul``
share the regime but generalise the equation: see ``eq12_plan``.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cdc_matmul import LANE, col_block, k_block, mxu_dot


def _decode_kernel(valid_ref, y_ref, p_ref, o_ref):
    # y_ref: [T, bm, bn]; p_ref: [1, bm, bn]; valid_ref: [T]
    y = y_ref[...].astype(jnp.float32)
    valid = valid_ref[...]
    vmask = valid.astype(jnp.float32)[:, None, None]
    zeroed = y * vmask                       # kill garbage in erased slots
    total = jnp.sum(zeroed, axis=0)          # sum of the valid shards
    missing = p_ref[0].astype(jnp.float32) - total  # Eq. 12
    out = zeroed + (1.0 - vmask) * missing[None]
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def cdc_decode_pallas(y_shards: jax.Array, parity: jax.Array,
                      valid: jax.Array, *, bm: int = 128, bn: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Recover <=1 erased shard. y: [T, m, n], parity: [m, n], valid: [T]."""
    t, m, n = y_shards.shape
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    return pl.pallas_call(
        _decode_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((t,), lambda i, j: (0,)),
            pl.BlockSpec((t, bm, bn), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((t, bm, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((t, m, n), y_shards.dtype),
        interpret=interpret,
    )(valid, y_shards, parity[None])


# ------------------------------------------------------ fused head+argmax ----

NEG_INF = -1e30  # python float: jnp scalars would be captured consts
_NO_ID = 2 ** 31 - 1   # id that loses every min()


def _fused_head_kernel(valid_ref, x_ref, w_ref, pw_ref, oval_ref, oidx_ref,
                       acc_ref, *, m_l: int, bn: int, vocab: int):
    """One (vocab tile j, contraction tile kk) step of the fused coded
    head: accumulate the T shard GEMMs and the sum-parity GEMM over k,
    then at the last k step Eq. 12-decode and fold the tile into the
    running argmax. The (b, 1) output blocks are revisited at every step
    and carry the running (max logit, global argmax) across tiles."""
    j, kk = pl.program_id(0), pl.program_id(1)
    T = w_ref.shape[0]

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # coded matmul: every shard's tile plus the sum-parity tile (MXU)
    x = x_ref[...]                                 # [b, bk]
    for t in range(T):
        acc_ref[t] += mxu_dot(x, w_ref[t])
    acc_ref[T] += mxu_dot(x, pw_ref[...])

    @pl.when(kk == pl.num_programs(1) - 1)
    def _():
        # parity decode (Eq. 12): zero the erased shard, rebuild it from
        # the sum parity
        alive = [valid_ref[t] != 0 for t in range(T)]
        yz = [jnp.where(alive[t], acc_ref[t], 0.0) for t in range(T)]
        missing = acc_ref[T]
        for t in range(T):
            missing = missing - yz[t]
        # shard t's tile covers merged-vocab ids t*m_l + j*bn + c; columns
        # past m_l (the overhanging last tile) or the vocab never win
        col = j * bn + jax.lax.broadcasted_iota(jnp.int32, missing.shape, 1)
        nv = ni = None
        for t in range(T):
            gid = t * m_l + col
            logit = jnp.where((col < m_l) & (gid < vocab),
                              jnp.where(alive[t], yz[t], missing), NEG_INF)
            v = jnp.max(logit, axis=1, keepdims=True)          # [b, 1]
            i = jnp.min(jnp.where(logit == v, gid, _NO_ID), axis=1,
                        keepdims=True)
            if nv is None:
                nv, ni = v, i
            else:
                # ids grow with t inside a tile: ties keep the earlier one
                better = v > nv
                nv, ni = jnp.where(better, v, nv), jnp.where(better, i, ni)

        @pl.when(j == 0)
        def _():
            oval_ref[...] = nv
            oidx_ref[...] = ni

        @pl.when(j > 0)
        def _():
            cv, ci = oval_ref[...], oidx_ref[...]
            # strict argmax semantics: ties go to the smaller global id
            better = (nv > cv) | ((nv == cv) & (ni < ci))
            oval_ref[...] = jnp.where(better, nv, cv)
            oidx_ref[...] = jnp.where(better, ni, ci)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "shard_width", "bn", "bk",
                                    "interpret"))
def cdc_fused_head_argmax_pallas(x: jax.Array, w_shards: jax.Array,
                                 parity_w: jax.Array, valid: jax.Array, *,
                                 vocab: int, shard_width: int | None = None,
                                 bn: int = 512, bk: int = 512,
                                 interpret: bool = False
                                 ) -> tuple[jax.Array, jax.Array]:
    """Fused coded LM head + parity decode + greedy argmax.

    x:        [b, k] last-position hidden states (post final norm).
    w_shards: [T, k, m_l] column shards of the (padded) head weight.
    parity_w: [k, m_l] sum-parity head weight (generator row 0, all-ones).
    valid:    [T] bool shard validity; at most ONE False (Eq. 12 regime —
              the caller falls back to the reference MDS path beyond that).
    vocab:    logical vocabulary (merged columns >= vocab never win).
    shard_width: logical m_l when the shards carry zero columns past it
              (``pad_head_shards``); those columns never win.
    bn/bk:    requested column and contraction tiles, rounded to legal
              TPU tiles (``cdc_matmul.col_block`` / ``k_block``).

    Returns (token [b] int32, max_logit [b] f32) — argmax over the merged
    [b, T*m_l] logits, which are never materialised.
    """
    t, k, cols = w_shards.shape
    b = x.shape[0]
    bn, bk = col_block(cols, bn), k_block(k, bk)
    kernel = functools.partial(_fused_head_kernel, m_l=shard_width or cols,
                               bn=bn, vocab=vocab)
    val, idx = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(cols, bn), k // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((b, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((t, bk, bn), lambda j, kk: (0, kk, j)),
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((b, 1), lambda j, kk: (0, 0)),
            pl.BlockSpec((b, 1), lambda j, kk: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((t + 1, b, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(valid.astype(jnp.int32), x, w_shards, parity_w)
    return idx[:, 0], val[:, 0]


def pad_head_shards(w_shards: jax.Array, parity_w: jax.Array):
    """Zero-pad the head shards' columns to a multiple of 128 lanes, once,
    outside the round: a lane-aligned [k, cols] operand keeps the TPU's
    row-major tiled layout, where an unaligned one is laid out
    column-major by XLA and copied back on every call. Pass the original
    m_l as ``shard_width``."""
    pad = -w_shards.shape[-1] % LANE
    if not pad:
        return w_shards, parity_w
    return (jnp.pad(w_shards, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(parity_w, ((0, 0), (0, pad))))

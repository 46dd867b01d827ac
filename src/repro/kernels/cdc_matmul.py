"""Fused Pallas kernels for the IN-BODY coded decode round.

The Pallas fast path used to end at the LM head (``cdc_decode.py``): every
in-body coded GEMM — attention QKV, FFN up/gate, and their erasure
recovery — still round-tripped T shard outputs plus r parity outputs
through HBM on the reference path, then re-read them for the Eq. 12
decode and the merge. These kernels close that gap: ONE kernel computes
the T shard GEMMs and the parity GEMMs tile-by-tile, applies the paper's
Eq. 12 parity reconstruction for masked shards in-register, and writes
the MERGED activation directly — per-shard outputs never exist in HBM.

``cdc_coded_matmul_pallas`` — fused coded matmul + decode + merge:
    x [rows, k] @ w_shards [T, k, m_l] (+ parity_w [r, k, m_l])
      -> merged [rows, T, m_l]      (reshape to [rows, T*m_l] is free:
                                     the kernel writes merge order directly)
  Optionally folds the preceding RMSNorm into the same VMEM pass
  (``gamma`` — the stretch fusion: norm + coded GEMM + decode + merge).

``cdc_decode_merge_pallas`` — decode-and-merge of ALREADY-computed shard
outputs (the ``core.decode_and_merge`` tail, e.g. outputs gathered by
``dist.collectives``): ys [T, rows, m_l] + parity [r, rows, m_l]
-> merged [rows, T, m_l], same in-register Eq. 12 pass.

Erasure regime (both kernels): at most ONE erased shard — the paper's
Eq. 12 sum-code recovery, generalised to any generator row via a
per-column equation plan (``eq12_plan``). For the folded/staggered parity
placement a dead device also kills one parity *slice* per equation, so
the plan selects, per output column, the lowest-index parity equation
whose slice survived (exactly ``decode_folded``'s top-1 selection) and
bakes the 1/gen[e, d] back-substitution coefficient in. Beyond one
erasure the callers (``kernels.ops``, ``executor.vstep``) fall back to
the reference MDS path — never a silent wrong answer.

Tiling (what Mosaic lays out on a TPU): grid (rows/bm, m_l/bn, k/bk) with
the contraction axis last and sequential. Column tiles are 128-lane
multiples (``col_block``; the last tile may overhang m_l, and Pallas
masks its stores) or, under 128 lanes, the whole shard width; the k axis streams (T+r) weight blocks of [bk, bn] into an
f32 VMEM accumulator, so fast memory stays bounded for any k:
  VMEM bytes ~= 2*(bm*bk + (T+r)*bk*bn)*itemsize      (double-buffered in)
              + (T+r)*bm*bn*4                          (f32 accumulator)
              + 2*bm*T*bn*itemsize                     (double-buffered out)
The validity mask and the [r, T] generator live in SMEM as scalars; the
per-column plan is a [1, bn] int32/f32 lane vector; each shard's [bm, bn]
slab is stored straight into ``o_ref[:, t, :]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coded_layer import folded_slot_map

LANE = 128      # TPU vector lane width: the minor dim of every tile
SUBLANE = 16    # rows per tile, safe for f32 (8) and bf16 (16)


def col_block(m: int, bn: int) -> int:
    """Lane-minor tile for a dim of width ``m``: a multiple of 128 lanes
    no larger than ``bn`` or ``m`` that leaves the least overhang in the
    last tile (ties go to the larger tile). A dim under 128 lanes is taken
    whole. The grid is ``cdiv(m, tile)``: Pallas masks the stores of the
    overhanging tile, and its columns never mix with others."""
    if m < LANE:
        return m
    top = min(max(LANE, bn - bn % LANE), m - m % LANE)
    return min(range(LANE, top + 1, LANE),
               key=lambda b: (pl.cdiv(m, b) * b, -b))


def k_block(k: int, bk: int) -> int:
    """Contraction tile: the largest 128-multiple <= bk dividing k, or the
    whole k when it is small or not lane-aligned."""
    if k <= bk or k % LANE:
        return k
    return max(b for b in range(LANE, bk + 1, LANE) if k % b == 0)


def row_block(rows: int, bm: int) -> int:
    """Sublane tile: all rows when they fit one tile, else a multiple of
    16 (legal for f32 and bf16); the last tile may overhang."""
    bm = max(SUBLANE, bm - bm % SUBLANE)
    return rows if rows <= bm else bm


def mxu_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w on the MXU in the weight's dtype with f32 accumulation; f32
    weights get full f32 precision (TPU's default f32 dot is one bf16
    pass)."""
    prec = jax.lax.Precision.HIGHEST if w.dtype == jnp.float32 else None
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32,
                   precision=prec)


def eq12_plan(spec, valid: jax.Array, valid_parity: jax.Array,
              m_l: int) -> tuple[jax.Array, jax.Array]:
    """Per-output-column decode plan for the <=1-erasure regime.

    Returns (esel [m_l] int32, coef [m_l] f32): column c of a missing
    shard d is rebuilt as  coef[c] * (p_{esel[c]} - sum_i gen[esel[c],i]
    * y_i)  with coef = 1/gen[esel[c], d]. Dedicated layout (parity rows
    intact) always uses the sum row (esel=0, coef=1). Folded layout picks,
    per slice, the lowest-index equation whose staggered parity slice is
    still on a healthy device — the same top-1 selection as
    ``core.decode_folded``, so fused ≡ reference under every in-budget
    mask. Fully traceable: the mask stays a runtime array.
    """
    code = spec.code
    T, r = code.n_shards, code.n_parity
    gen = jnp.asarray(code.generator, jnp.float32)          # [r, T]
    d = jnp.argmin(valid)               # first dead shard (0 if none dead)
    if spec.layout == "folded" and r > 1 and m_l % T == 0:
        w = m_l // T
        smap = jnp.asarray(folded_slot_map(T, r))           # [r, T]
        pv = valid_parity[smap]                             # [r, T] alive?
        eq_score = jnp.where(pv, 1.0, -1.0) \
            - jnp.arange(r, dtype=jnp.float32)[:, None] * 1e-3
        esel = jnp.repeat(jnp.argmax(eq_score, axis=0).astype(jnp.int32),
                          w, total_repeat_length=m_l)
    else:
        esel = jnp.zeros((m_l,), jnp.int32)
    coef = (1.0 / gen[esel, d]).astype(jnp.float32)         # [m_l]
    return esel, coef


def _plan_operands(valid, gen, esel, coef):
    """Kernel-side forms of the plan: the mask as int32 and the generator
    as f32 scalars (SMEM), the per-column plan as [1, m_l] lane rows."""
    return (valid.astype(jnp.int32), gen.astype(jnp.float32),
            esel.astype(jnp.int32)[None, :],
            coef.astype(jnp.float32)[None, :])


def _eq12_store(o_ref, ys, ps, valid_ref, gen_ref, esel, coef):
    """Shared in-register tail: zero dead shards, Eq. 12-reconstruct the
    missing one from its selected parity equation, and store each shard's
    [bm, bn] slab at ``o_ref[:, t, :]`` (merge order).

    ys: T f32 [bm, bn] shard tiles; ps: r f32 [bm, bn] parity tiles;
    valid_ref [T] int32 and gen_ref [r, T] f32 in SMEM; esel/coef [1, bn].
    """
    T, r = len(ys), len(ps)
    alive = [valid_ref[t] != 0 for t in range(T)]
    yz = [jnp.where(alive[t], ys[t], 0.0) for t in range(T)]
    pick = jnp.zeros_like(yz[0])
    for e in range(r):
        # residual_e = p_e - sum_i gen[e, i] * y_i  (dead shards zeroed)
        res = ps[e]
        for t in range(T):
            res = res - gen_ref[e, t] * yz[t]
        # per-column equation pick without NaN propagation from rows that
        # are never selected: where(), not a multiply-by-onehot
        pick = jnp.where(esel == e, res, pick)
    missing = pick * coef
    for t in range(T):
        o_ref[:, t, :] = jnp.where(alive[t], yz[t],
                                   missing).astype(o_ref.dtype)


# ------------------------------------------------- fused coded matmul ----

def _coded_matmul_kernel(valid_ref, gen_ref, esel_ref, coef_ref, x_ref,
                         w_ref, pw_ref, *rest, fuse_norm: bool, eps: float):
    if fuse_norm:
        xs_ref, gamma_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    T, r = w_ref.shape[0], pw_ref.shape[0]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)                      # [bm, bk]
    if fuse_norm:
        xs = xs_ref[...].astype(jnp.float32)                # [bm, k]
        var = jnp.mean(xs * xs, axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + eps) \
            * gamma_ref[...].astype(jnp.float32)
    # the T shard GEMMs + the r parity GEMMs for this tile (MXU)
    for t in range(T):
        acc_ref[t] += mxu_dot(x, w_ref[t])
    for e in range(r):
        acc_ref[T + e] += mxu_dot(x, pw_ref[e])

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        _eq12_store(o_ref, [acc_ref[t] for t in range(T)],
                    [acc_ref[T + e] for e in range(r)], valid_ref, gen_ref,
                    esel_ref[...], coef_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "eps",
                                             "out_dtype", "interpret"))
def cdc_coded_matmul_pallas(x: jax.Array, w_shards: jax.Array,
                            parity_w: jax.Array, gen: jax.Array,
                            esel: jax.Array, coef: jax.Array,
                            valid: jax.Array, *, gamma: jax.Array | None
                            = None, eps: float = 1e-5, bm: int = 128,
                            bn: int = 256, bk: int = 512, out_dtype=None,
                            interpret: bool = False) -> jax.Array:
    """Fused (rmsnorm?) + coded shard GEMMs + Eq. 12 decode + merge.

    x:        [rows, k] activations (pre-norm when ``gamma`` is given).
    w_shards: [T, k, m_l] column shards of the weight.
    parity_w: [r, k, m_l] parity weights in UNFOLDED/dedicated layout
              (callers unfold the slot-major folded layout first).
    gen:      [r, T] generator rows; esel/coef: the ``eq12_plan``.
    valid:    [T] bool; at most ONE False (callers fall back beyond).
    bm/bn/bk: requested tiles, rounded to legal TPU tiles (``row_block``,
              ``col_block``, ``k_block``).

    Returns merged [rows, T, m_l] — ``reshape(rows, T*m_l)`` IS the
    merged activation (merge order is written directly; no transpose,
    no per-shard HBM array ever exists).
    """
    rows, k = x.shape
    t, k2, m_l = w_shards.shape
    r = parity_w.shape[0]
    assert k == k2, (x.shape, w_shards.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = row_block(rows, bm), col_block(m_l, bn), k_block(k, bk)
    fuse_norm = gamma is not None
    kernel = functools.partial(_coded_matmul_kernel, fuse_norm=fuse_norm,
                               eps=eps)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),               # valid [T]
        pl.BlockSpec(memory_space=pltpu.SMEM),               # gen [r, T]
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),      # esel
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),      # coef
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((t, bk, bn), lambda i, j, kk: (0, kk, j)),
        pl.BlockSpec((r, bk, bn), lambda i, j, kk: (0, kk, j)),
    ]
    args = [*_plan_operands(valid, gen, esel, coef), x, w_shards, parity_w]
    if fuse_norm:
        # the whole row for the norm statistics, gamma tiled with k
        in_specs += [pl.BlockSpec((bm, k), lambda i, j, kk: (i, 0)),
                     pl.BlockSpec((1, bk), lambda i, j, kk: (0, kk))]
        args += [x, gamma.reshape(1, k)]
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, bm), pl.cdiv(m_l, bn), k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, t, bn), lambda i, j, kk: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, t, m_l), out_dtype),
        scratch_shapes=[pltpu.VMEM((t + r, bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


# --------------------------------------------------- decode-and-merge ----

def _decode_merge_kernel(valid_ref, gen_ref, esel_ref, coef_ref, y_ref,
                         p_ref, o_ref):
    T, r = y_ref.shape[0], p_ref.shape[0]
    _eq12_store(o_ref, [y_ref[t].astype(jnp.float32) for t in range(T)],
                [p_ref[e].astype(jnp.float32) for e in range(r)],
                valid_ref, gen_ref, esel_ref[...], coef_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "out_dtype",
                                             "interpret"))
def cdc_decode_merge_pallas(ys: jax.Array, parity: jax.Array,
                            gen: jax.Array, esel: jax.Array,
                            coef: jax.Array, valid: jax.Array, *,
                            bm: int = 128, bn: int = 512, out_dtype=None,
                            interpret: bool = False) -> jax.Array:
    """Eq. 12 decode + merge of already-computed shard outputs.

    ys: [T, rows, m_l] shard outputs; parity: [r, rows, m_l] UNFOLDED
    parity outputs; valid: [T] bool, at most one False. Returns merged
    [rows, T, m_l] (reshape to [rows, T*m_l] is free). One fused
    elementwise pass: the stacked shard outputs are read once and only
    the merged activation is written.
    """
    t, rows, m_l = ys.shape
    r = parity.shape[0]
    out_dtype = out_dtype or ys.dtype
    bm, bn = row_block(rows, bm), col_block(m_l, bn)
    return pl.pallas_call(
        _decode_merge_kernel,
        grid=(pl.cdiv(rows, bm), pl.cdiv(m_l, bn)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((t, bm, bn), lambda i, j: (0, i, j)),
            pl.BlockSpec((r, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bm, t, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, t, m_l), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*_plan_operands(valid, gen, esel, coef), ys, parity)

"""CDC-coded column-parallel (output-split) GEMM.

This is the paper's contribution as a composable JAX primitive. A coded dense
layer owns:
  w      [k, m]              the ordinary weight, column-sharded over `model`
  w_cdc  [T, k, r*m_l/T]     folded parity weights (slot-major, staggered), or
         [r, k, m_l]         dedicated parity weights (paper layout)
with m_l = m / T. Parity weights are computed OFFLINE from w (paper §5.2:
"the summation of the weights ... is not dependent on inputs").

Two placements (DESIGN.md §2):
  * ``dedicated`` -- the paper's +r-devices scheme: parity shards live on
    their own shard slots (natural across a DCN/pod axis, or test meshes of
    size T+r). Tolerates r erasures at +r/T compute.
  * ``folded`` -- TPU-native: each of the T devices computes its data shard
    plus a 1/T slice of every parity shard, with slice->device assignment
    STAGGERED so one device failure destroys at most one parity equation per
    output column. Tolerates floor(r/2) whole-device failures (r=2 covers the
    paper's single-failure case) at +r/T compute, on an unmodified 2^k mesh.

All math is expressed as plain jnp ops over an explicit shard dimension, so it
runs identically on one CPU device (smoke tests / oracles) and under GSPMD on
a production mesh (``dist.sharding`` pins the layouts); a shard_map wrapper
with explicit per-device placement lives in ``dist.collectives``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coding
from repro.core.coding import CodeSpec

__all__ = [
    "CodedLayout",
    "pad_for_code",
    "make_parity_weights",
    "fold_parity_slots",
    "unfold_parity",
    "folded_slot_map",
    "coded_matmul",
    "decode_folded",
    "decode_and_merge",
    "merge_shards",
    "CodedDenseSpec",
]


@dataclasses.dataclass(frozen=True)
class CodedDenseSpec:
    """Static description of one coded GEMM."""

    code: CodeSpec
    layout: str = "folded"  # "folded" | "dedicated"

    def __post_init__(self):
        if self.layout not in ("folded", "dedicated"):
            raise ValueError(self.layout)
        if self.layout == "folded" and self.code.n_parity > 0:
            # folded slices must divide the shard width; checked at encode.
            pass

    @property
    def max_device_failures(self) -> int:
        if self.code.n_parity == 0:
            return 0
        if self.layout == "dedicated":
            return self.code.n_parity
        return self.code.n_parity // 2


CodedLayout = CodedDenseSpec  # alias


def _fused_enabled(use_fused: bool | str) -> bool:
    """The shared fused-kernel policy: ``"auto"`` enables the Pallas path
    only where it compiles natively (TPU); True forces it (interpret mode
    elsewhere — the conformance suites); False is the plain-jnp reference."""
    if use_fused == "auto":
        return jax.default_backend() == "tpu"
    return bool(use_fused)


def pad_for_code(m: int, n_shards: int, align: int = 8) -> int:
    """Round output dim up so m % (T*T*align) == 0 (shard width divides into
    T aligned parity slices). align=128 for MXU-friendly production dims."""
    q = n_shards * n_shards * align
    return ((m + q - 1) // q) * q


def make_parity_weights(w: jax.Array, spec: CodedDenseSpec) -> jax.Array:
    """Offline encode. w: [k, m] -> dedicated [r, k, m_l] or folded slots
    [T, k, r*m_l/T]."""
    code = spec.code
    T, r = code.n_shards, code.n_parity
    if w.ndim == 3:  # stacked layers [L, k, m] (scan-over-layers params)
        import jax as _jax
        return _jax.vmap(lambda wi: make_parity_weights(wi, spec))(w)
    k, m = w.shape
    if m % T:
        raise ValueError(f"output dim {m} not divisible by T={T}; "
                         f"pad with pad_for_code() first")
    m_l = m // T
    shards = jnp.moveaxis(w.reshape(k, T, m_l), 1, 0)  # [T, k, m_l]
    parity = coding.encode_weights(shards, code)  # [r, k, m_l]
    if spec.layout == "dedicated":
        return parity
    return fold_parity_slots(parity, T)


def folded_slot_map(T: int, r: int) -> np.ndarray:
    """slot_map[j, s] = device slot holding slice s of parity j (staggered).

    Chosen so slot d computes slice (d - j - 1) mod T of parity j: a failure
    of device d erases, for each output column, at most ONE parity equation
    (the one whose slice landed on d), never the same one twice.
    """
    j = np.arange(r)[:, None]
    s = np.arange(T)[None, :]
    return (s + j + 1) % T


def fold_parity_slots(parity: jax.Array, T: int) -> jax.Array:
    """[r, k, m_l] -> [T, k, r*w] slot-major staggered layout, w = m_l/T."""
    r, k, m_l = parity.shape
    if m_l % T:
        raise ValueError(f"shard width {m_l} not divisible by T={T} "
                         f"(pad_for_code)")
    w = m_l // T
    sliced = parity.reshape(r, k, T, w)  # [r, k, s, w]
    smap = folded_slot_map(T, r)  # [r, T]
    # slot d, parity j holds slice s where smap[j, s] == d  =>  s = (d - j - 1) % T
    j = np.arange(r)[:, None]
    d = np.arange(T)[None, :]
    s_of = (d - j - 1) % T  # [r, T] slice index for (j, slot)
    # gather: out[d, k, j, w] = sliced[j, k, s_of[j, d], w]
    out = sliced[j[:, 0][:, None, None, None],
                 np.arange(k)[None, :, None, None],
                 s_of[:, None, :, None],
                 np.arange(w)[None, None, None, :]]  # [r, k, T, w]
    out = jnp.moveaxis(out, 2, 0)  # [T, r, k, w] -> want [T, k, r*w]
    out = jnp.moveaxis(out, 1, 2).reshape(T, k, r * w)
    return out


def unfold_parity(p_slots: jax.Array, T: int, r: int) -> jax.Array:
    """Inverse of the slot layout for *outputs*: [T, ..., r*w] -> [r, ..., m_l].

    p_slots[d][..., j*w:(j+1)*w] is slice (d-j-1)%T of parity j.
    """
    w = p_slots.shape[-1] // r
    parts = p_slots.reshape(p_slots.shape[:-1] + (r, w))  # [T, ..., r, w]
    parts = jnp.moveaxis(parts, -2, 1)  # [T, r, ..., w]
    smap = folded_slot_map(T, r)  # slot holding slice s of parity j
    # parity[j, s] = parts[smap[j, s], j]
    gathered = parts[jnp.asarray(smap), jnp.arange(r)[:, None]]  # [r, T, ..., w]
    # reassemble slices along the last dim: [r, ..., T*w]
    gathered = jnp.moveaxis(gathered, 1, -2)  # [r, ..., T, w]
    return gathered.reshape(gathered.shape[:-2] + (T * w,))


def _shardwise_matmul(x: jax.Array, w_stacked: jax.Array,
                      dtype=None) -> jax.Array:
    """y[d] = x @ w_stacked[d];  x: [..., k], w: [T, k, c] -> [T, ..., c].
    Full precision, so f32 parity weights stay f32 on a TPU."""
    return jnp.einsum("...k,dkc->d...c", x, w_stacked,
                      preferred_element_type=dtype or x.dtype,
                      precision=coding.EXACT)


def merge_shards(ys: jax.Array) -> jax.Array:
    """[T, ..., m_l] stacked shard outputs -> merged [..., T*m_l]."""
    y = jnp.moveaxis(ys, 0, -2)
    return y.reshape(y.shape[:-2] + (y.shape[-2] * y.shape[-1],))


def decode_and_merge(
    ys: jax.Array,
    parity: jax.Array | None,
    spec: CodedDenseSpec,
    valid: jax.Array | None,
    *,
    valid_parity: jax.Array | None = None,
    acc_dtype=jnp.float32,
    use_fused: bool | str = False,
) -> jax.Array:
    """Recovery + merge given already-computed shard outputs.

    The tail of ``coded_matmul`` — shared with ``dist.collectives``, where
    ``ys``/``parity`` arrive from an all_gather over the `model` axis
    instead of a local stacked einsum. Erased entries of ``ys`` (and, for
    the folded layout, dead slots of ``parity``) may hold garbage; they
    are masked here before the decode. Dedicated-layout parity rows are
    assumed INTACT: ``coding.decode_outputs`` solves with all r equations
    and has no equation-selection for a lost parity message (the folded
    path does, via ``valid_parity``) — dedicated callers must deliver
    parity from healthy workers (coded_matmul recomputes it locally).

    ys:     [T, ..., m_l] data-shard outputs.
    parity: [r, ..., m_l] (dedicated) or [T, ..., r*w] slots (folded);
            None => plain merge.
    """
    code = spec.code
    T = code.n_shards
    if parity is None or code.n_parity == 0 or valid is None:
        return merge_shards(ys)
    if _fused_enabled(use_fused):
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.fused_decode_merge(ys, parity, spec, valid,
                                      valid_parity=valid_parity)
    if valid_parity is None:
        valid_parity = valid
    vshape = (T,) + (1,) * (ys.ndim - 1)
    ys = jnp.where(valid.reshape(vshape), ys, 0)
    if spec.layout == "dedicated":
        rec = coding.decode_outputs(ys, parity, valid, code)
    else:
        pshape = (T,) + (1,) * (parity.ndim - 1)
        p_slots = jnp.where(valid_parity.reshape(pshape), parity, 0)
        rec = decode_folded(ys, p_slots, valid, code,
                            valid_parity=valid_parity, acc_dtype=acc_dtype)
    return merge_shards(rec)


def coded_matmul(
    x: jax.Array,
    w: jax.Array,
    w_cdc: jax.Array | None,
    spec: CodedDenseSpec,
    valid: jax.Array | None = None,
    *,
    valid_parity: jax.Array | None = None,
    acc_dtype=jnp.float32,
    use_fused: bool | str = False,
) -> jax.Array:
    """Output-split GEMM with CDC protection (paper Eq. 7/11 + recovery 12).

    Args:
      x: [..., k] activations (replicated over the model axis).
      w: [k, m] weights (column-sharded over the model axis).
      w_cdc: parity weights from ``make_parity_weights`` (None => uncoded).
      spec: code + layout.
      valid: [T] bool device-validity mask (None => all valid). Erased shards'
        contributions are zeroed (simulating the lost message / dead device)
        and reconstructed from parity.
      valid_parity: validity of the parity *messages*. Defaults to ``valid``
        (whole-device failure: a dead device loses its data shard AND its
        folded parity slices). Pass all-ones for the message-erasure model,
        where r=1 folded already recovers a lost data message.
      use_fused: route through the fused Pallas kernel
        (``kernels.ops.fused_coded_matmul``): shard GEMMs + Eq. 12 decode +
        merge in one kernel, no per-shard HBM round-trips. ``"auto"`` =
        native TPU only; True forces (interpret elsewhere); False (default)
        = this reference path. The fused kernel covers the <=1-erasure
        regime and falls back here beyond it.

    Returns:
      [..., m] the full (merged) output, identical to x @ w when all shards
      are valid, and still identical (up to float eps) under <= f erasures.
    """
    code = spec.code
    T = code.n_shards
    if w_cdc is not None and code.n_parity > 0 and valid is not None \
            and _fused_enabled(use_fused):
        from repro.kernels import ops  # deferred: kernels import this module
        return ops.fused_coded_matmul(x, w, w_cdc, spec, valid,
                                      valid_parity=valid_parity)
    k, m = w.shape
    m_l = m // T
    w_st = jnp.moveaxis(w.reshape(k, T, m_l), 1, 0)  # [T, k, m_l]
    if w_cdc is None or code.n_parity == 0 or valid is None:
        return merge_shards(_shardwise_matmul(x, w_st))  # nothing to recover

    # shard and parity outputs stay in acc_dtype through the recovery and
    # are rounded to the activation dtype once, after the merge: parity
    # minus the surviving shards cancels, and a bf16 round before it
    # would cost the recovered shard several ulps
    ys = _shardwise_matmul(x, w_st, acc_dtype)           # [T, ..., m_l]
    parity = _shardwise_matmul(x, w_cdc, acc_dtype)  # dedicated | slots
    out = decode_and_merge(ys, parity, spec, valid,
                           valid_parity=valid_parity, acc_dtype=acc_dtype)
    return out.astype(x.dtype)


def decode_folded(ys: jax.Array, p_slots: jax.Array, valid: jax.Array,
                  code: CodeSpec, *, valid_parity: jax.Array | None = None,
                  acc_dtype=jnp.float32) -> jax.Array:
    """Recover erased data shards under the folded/staggered placement.

    ys:      [T, ..., m_l] data-shard outputs (erased entries zeroed).
    p_slots: [T, ..., r*w] parity outputs in slot layout (erased zeroed).
    valid:   [T] device validity; at most floor(r/2) False.

    Per output column in slice s, the parity equations still alive are those
    j with valid[slot_map[j, s]]; each failed device kills exactly one
    equation per column. We solve, per slice, an f x f system (f = max
    failures) with the same static-shape top_k selection as
    ``coding.decode_outputs``.
    """
    T, r = code.n_shards, code.n_parity
    f = max(r // 2, 1)
    m_l = ys.shape[-1]
    w = m_l // T
    dtype = acc_dtype or ys.dtype
    if valid_parity is None:
        valid_parity = valid

    parity = unfold_parity(p_slots, T, r).astype(dtype)  # [r, ..., m_l]
    gen = jnp.asarray(code.generator, dtype=dtype)  # [r, T]
    y = ys.astype(dtype)

    # residual_j = parity_j - sum_{i valid} gen[j,i] y_i  (valid y already
    # zeroed-out for dead i, so plain tensordot works)
    residual = parity - jnp.tensordot(gen, y, axes=[[1], [0]],
                                      precision=coding.EXACT)  # [r, ..., m_l]

    smap = jnp.asarray(folded_slot_map(T, r))  # [r, T(slices)]
    pv = valid_parity[smap]  # [r, T] parity validity per slice

    # unknowns: up to f missing data shards (same for every slice/column)
    miss_score = jnp.where(valid, -1.0, 1.0)
    _, miss_idx = jax.lax.top_k(miss_score, f)  # [f]
    is_real = ~valid[miss_idx]  # [f]

    # equations: per slice, pick f valid parity rows (prefer low j)
    eq_score = jnp.where(pv, 1.0, -1.0) \
        - jnp.arange(r, dtype=jnp.float32)[:, None] * 1e-3
    _, eq_idx = jax.lax.top_k(eq_score.T, f)  # [T(slices), f]

    # per-slice f x f system: A[s, e, u] = gen[eq_idx[s,e], miss_idx[u]]
    A = gen[eq_idx][..., miss_idx]  # [S, f, f]
    eye = jnp.eye(f, dtype=dtype)
    A = jnp.where(is_real[None, None, :], A, eye[None])

    # rhs: residual of the selected equations, per slice
    res_sliced = residual.reshape((r,) + residual.shape[1:-1] + (T, w))
    res_sliced = jnp.moveaxis(res_sliced, -2, 1)  # [r, S, ..., w]
    rhs = jnp.take_along_axis(
        res_sliced, eq_idx.T.reshape((f, T) + (1,) * (res_sliced.ndim - 2)),
        axis=0)  # [f, S, ..., w]
    rhs = jnp.where(is_real.reshape((f,) + (1,) * (rhs.ndim - 1)), rhs, 0)

    # solve per slice: [S, f, f] @ sol[S, f, K] = rhs[S, f, K]
    K = int(np.prod(rhs.shape[2:]))
    rhs_flat = jnp.moveaxis(rhs, 0, 1).reshape(T, f, K)
    sol = jnp.linalg.solve(A, rhs_flat)  # [S, f, K]
    sol = jnp.moveaxis(sol.reshape((T, f) + rhs.shape[2:]), 1, 0)  # [f,S,...,w]

    # scatter the recovered slices back into y[miss_idx]
    upd = jnp.where(is_real.reshape((f,) + (1,) * (sol.ndim - 1)), sol, 0)
    y_sliced = y.reshape(y.shape[:-1] + (T, w))
    y_sliced = jnp.moveaxis(y_sliced, -2, 1)  # [T(shards), S, ..., w]
    y_sliced = y_sliced.at[miss_idx].add(upd)
    y_out = jnp.moveaxis(y_sliced, 1, -2).reshape(y.shape)
    return y_out.astype(ys.dtype)

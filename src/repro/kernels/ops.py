"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; off the TPU they execute in
``interpret=True`` mode (the kernel body runs in Python on CPU) so every test
and benchmark exercises the real kernel logic. ``use_pallas=False`` (or
backends where even interpret is undesirable for perf) falls back to the
ref oracle -- identical math, so the swap is safe.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.cdc_decode import (cdc_decode_pallas,
                                      cdc_fused_head_argmax_pallas)
from repro.kernels.cdc_encode import cdc_encode_pallas
from repro.kernels.cdc_matmul import (cdc_coded_matmul_pallas,
                                      cdc_decode_merge_pallas, eq12_plan)
from repro.kernels.matmul import matmul_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------- kernel cost model ----
#
# On TPU a pallas_call lowers to an opaque ``custom-call`` whose HLO carries
# no dot ops, so ``roofline.hlo_cost.analyze_hlo`` would count ~0 FLOPs for
# the fused round (interpret mode on CPU inlines the kernel body into
# ordinary dots and needs none of this). Each kernel therefore registers a
# pure shape-based FLOP model keyed by its jitted wrapper name — the name
# appears verbatim in the custom-call's ``metadata={op_name=...}`` — and the
# analyzer adds the modelled FLOPs to that instruction. Bytes stay with the
# analyzer's generic operands+output accounting (the custom-call boundary IS
# the HBM round trip), so nothing is double-counted.
#
# Cost fns take (out_shapes, operand_shapes) — each a list of (dtype,
# [dims]) in instruction order — and return dot-equivalent FLOPs, matching
# what the inlined interpret-mode HLO reports for the same kernel.

def _elems(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _cost_matmul(out, operands):
    # x [m, k] @ w [k, n] -> [m, n]
    if not out or len(out[0][1]) != 2 or not operands:
        return 0.0
    m, n = out[0][1]
    k = operands[0][1][-1] if operands[0][1] else 0
    return 2.0 * m * n * k


def _cost_cdc_encode(out, operands):
    # parity [r, ...] = gen [r, T] @ shards: 2 * out_elems * T
    t = next((d[1] for _, d in operands if len(d) == 2), 0)
    return 2.0 * sum(_elems(d) for _, d in out) * t


def _cost_cdc_coded_matmul(out, operands):
    # operand order: [valid, gen, esel, coef, x, w_shards, parity_w,
    # (x, gamma)?]
    # out [rows, T, m_l]; T+r shard GEMMs of x [rows, k] @ [k, m_l]
    if not out or len(out[0][1]) != 3:
        return 0.0
    rows, t, m_l = out[0][1]
    rank3 = [d for _, d in operands if len(d) == 3]
    if len(rank3) < 2:
        return 0.0
    k = rank3[0][1]            # w_shards [T, k, m_l]
    r = rank3[1][0]            # parity_w [r, k, m_l]
    return 2.0 * rows * k * m_l * (t + r)


def _cost_cdc_fused_head(out, operands):
    # operand order: [valid, x [b, k], w_shards [T, k, m_l], parity_w
    # [k, m_l]]; T shard GEMMs + 1 sum-parity GEMM of [b, k] @ [k, m_l]
    b = out[0][1][0] if out and out[0][1] else 0
    w = next((d for _, d in operands if len(d) == 3), None)
    if w is None:
        return 0.0
    t, k, m_l = w
    return 2.0 * b * k * m_l * (t + 1)


def _zero_cost(out, operands):
    # elementwise decode/normalize kernels: no dot FLOPs (consistent with
    # analyze_hlo counting only dot/convolution ops)
    return 0.0


#: jitted-wrapper name -> FLOP model; matched against custom-call lines by
#: LONGEST name containment (``matmul_pallas`` is a substring of
#: ``cdc_coded_matmul_pallas``).
KERNEL_COSTS: dict = {}


def register_kernel_cost(name: str, fn) -> None:
    """Register/overwrite the FLOP model for a Pallas kernel wrapper."""
    KERNEL_COSTS[name] = fn


for _name, _fn in (
        ("matmul_pallas", _cost_matmul),
        ("cdc_encode_pallas", _cost_cdc_encode),
        ("cdc_coded_matmul_pallas", _cost_cdc_coded_matmul),
        ("cdc_fused_head_argmax_pallas", _cost_cdc_fused_head),
        ("cdc_decode_merge_pallas", _zero_cost),
        ("cdc_decode_pallas", _zero_cost),
        ("rmsnorm_pallas", _zero_cost),
):
    register_kernel_cost(_name, _fn)


def _concrete_dead(valid) -> int | None:
    """Number of dead shards when the mask is host-concrete, else None.

    Traced masks (inside jit) cannot be counted at trace time — the
    <=1-erasure gate for the fused kernels then falls to the CALLER
    (``executor.vstep`` host-checks the mask before dispatching a fused
    round)."""
    if valid is None:
        return 0
    if isinstance(valid, jax.core.Tracer):
        return None
    v = np.asarray(valid)
    return int(v.size - v.sum())


def matmul(x, w, *, out_dtype=None, use_pallas=True, **block_kw):
    if not use_pallas:
        return ref.matmul_ref(x, w, out_dtype)
    return matmul_pallas(x, w, out_dtype=out_dtype, interpret=_interpret(),
                         **block_kw)


def cdc_encode(w_shards, gen, *, use_pallas=True, **block_kw):
    gen = jnp.asarray(gen, dtype=jnp.float32)
    if not use_pallas:
        return ref.cdc_encode_ref(w_shards, gen)
    return cdc_encode_pallas(w_shards, gen, interpret=_interpret(),
                             **block_kw)


def cdc_decode(y_shards, parity, valid, *, use_pallas=True, **block_kw):
    """r=1 Eq. 12 recovery combine; <=1 erased shard by construction.

    A host-concrete mask with 2+ erasures raises (a single sum parity
    cannot solve for two unknowns); the r>1 MDS layouts decode via
    ``core.coded_layer`` / ``fused_decode_merge`` instead.
    """
    dead = _concrete_dead(valid)
    if dead is not None and dead > 1:
        raise ValueError(
            f"cdc_decode is the r=1 Eq. 12 combine (one parity equation) "
            f"and recovers at most 1 erased shard, got {dead} dead")
    if not use_pallas:
        return ref.cdc_decode_ref(y_shards, parity, valid)
    return cdc_decode_pallas(y_shards, parity, valid,
                             interpret=_interpret(), **block_kw)


def fused_head_argmax(x, w_shards, parity_w, valid, *, vocab,
                      shard_width=None, use_pallas=True, **block_kw):
    """Fused coded LM-head GEMM + Eq. 12 parity decode + greedy argmax.

    The batched executor's decode hot path: one kernel per round, the
    merged [b, vocab] logits never hit HBM. Handles <= 1 erased shard
    (both the kernel and the ref oracle consume only the SUM parity row):
    a host-concrete mask with 2+ erasures raises instead of silently
    decoding garbage — multi-erasure rounds belong to the reference MDS
    path, which ``executor.vstep`` selects before dispatch (traced masks
    are the caller's contract for the same reason, see _concrete_dead).
    """
    dead = _concrete_dead(valid)
    if dead is not None and dead > 1:
        raise ValueError(
            f"fused_head_argmax recovers at most 1 erased shard (Eq. 12 "
            f"sum-parity regime), got {dead} dead; use the reference "
            f"decode path (full logits + MDS recovery) for this round")
    if not use_pallas:
        return ref.fused_head_argmax_ref(x, w_shards, parity_w, valid, vocab,
                                         shard_width)
    return cdc_fused_head_argmax_pallas(x, w_shards, parity_w, valid,
                                        vocab=vocab, shard_width=shard_width,
                                        interpret=_interpret(), **block_kw)


def fused_coded_matmul(x, w, w_cdc, spec, valid, *, valid_parity=None,
                       gamma=None, eps=1e-5, use_pallas=True,
                       out_dtype=None, **block_kw):
    """Fused in-body coded GEMM: (rmsnorm?) + T shard GEMMs + r parity
    GEMMs + Eq. 12 decode + merge in ONE kernel — per-shard outputs never
    round-trip HBM.

    x: [..., k]; w: [k, m] (column-sharded logical weight); w_cdc: parity
    weights in either layout (folded slots are unfolded host-side — the
    kernel always sees dedicated [r, k, m_l] parity). Returns the merged
    [..., m] activation, matching ``core.coded_matmul`` bit-close under
    every in-budget <=1-erasure mask.

    Fallback ladder (never a silent wrong answer):
      * host-concrete mask with 2+ dead  -> reference ``coded_matmul``
        (full MDS recovery, exact reference semantics);
      * traced mask -> kernel unconditionally; the caller must gate
        (vstep host-checks <=1 dead before dispatching a fused round);
      * ``use_pallas=False`` -> the ``ref.py`` oracle (same plan + math).
    """
    from repro.core import coded_layer
    code = spec.code
    T, r = code.n_shards, code.n_parity
    dead = _concrete_dead(valid)
    if w_cdc is None or r == 0 or valid is None \
            or (dead is not None and dead > 1):
        xn = ref.rmsnorm_ref(x, gamma, eps) if gamma is not None else x
        return coded_layer.coded_matmul(xn, w, w_cdc, spec, valid,
                                        valid_parity=valid_parity)
    valid = jnp.asarray(valid)
    if valid_parity is None:
        valid_parity = valid
    k, m = w.shape
    m_l = m // T
    w_st = jnp.moveaxis(w.reshape(k, T, m_l), 1, 0)        # [T, k, m_l]
    if spec.layout == "dedicated":
        pw = w_cdc                                         # [r, k, m_l]
    else:
        pw = coded_layer.unfold_parity(w_cdc, T, r)        # -> [r, k, m_l]
    gen = jnp.asarray(code.generator, jnp.float32)
    esel, coef = eq12_plan(spec, valid, valid_parity, m_l)
    lead = x.shape[:-1]
    xf = x.reshape(-1, k)
    if not use_pallas:
        out = ref.cdc_coded_matmul_ref(xf, w_st, pw, gen, esel, coef,
                                       valid, gamma=gamma, eps=eps,
                                       out_dtype=out_dtype)
    else:
        out = cdc_coded_matmul_pallas(xf, w_st, pw, gen, esel, coef, valid,
                                      gamma=gamma, eps=eps,
                                      out_dtype=out_dtype,
                                      interpret=_interpret(), **block_kw)
    return out.reshape(lead + (m,))


def fused_decode_merge(ys, parity, spec, valid, *, valid_parity=None,
                       use_pallas=True, out_dtype=None, **block_kw):
    """Fused Eq. 12 decode + merge of already-computed shard outputs —
    the ``core.decode_and_merge`` tail (e.g. outputs gathered by
    ``dist.collectives``) as one kernel pass.

    ys: [T, ..., m_l]; parity: dedicated [r, ..., m_l] or folded slots
    [T, ..., r*w] (unfolded host-side). Same <=1-erasure regime and
    fallback ladder as ``fused_coded_matmul``.
    """
    from repro.core import coded_layer
    code = spec.code
    T, r = code.n_shards, code.n_parity
    dead = _concrete_dead(valid)
    if parity is None or r == 0 or valid is None \
            or (dead is not None and dead > 1):
        return coded_layer.decode_and_merge(ys, parity, spec, valid,
                                            valid_parity=valid_parity)
    valid = jnp.asarray(valid)
    if valid_parity is None:
        valid_parity = valid
    m_l = ys.shape[-1]
    if spec.layout == "dedicated":
        par = parity                                       # [r, ..., m_l]
    else:
        par = coded_layer.unfold_parity(parity, T, r)      # -> [r, ..., m_l]
    gen = jnp.asarray(code.generator, jnp.float32)
    esel, coef = eq12_plan(spec, valid, valid_parity, m_l)
    mid = ys.shape[1:-1]
    ysf = ys.reshape(T, -1, m_l)
    parf = par.reshape(r, -1, m_l)
    if not use_pallas:
        out = ref.cdc_decode_merge_ref(ysf, parf, gen, esel, coef, valid,
                                       out_dtype=out_dtype)
    else:
        out = cdc_decode_merge_pallas(ysf, parf, gen, esel, coef, valid,
                                      out_dtype=out_dtype,
                                      interpret=_interpret(), **block_kw)
    return out.reshape(mid + (T * m_l,))


def rmsnorm(x, gamma, *, eps=1e-6, use_pallas=True, **block_kw):
    if not use_pallas:
        return ref.rmsnorm_ref(x, gamma, eps)
    return rmsnorm_pallas(x, gamma, eps=eps, interpret=_interpret(),
                          **block_kw)

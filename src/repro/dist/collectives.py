"""Explicit per-device coded GEMM via shard_map (DESIGN.md §4).

``core.coded_matmul`` expresses the paper's coded output-split GEMM as
logical stacked einsums and lets GSPMD place them. This module is the
explicit counterpart: ``coded_matmul_shardmap`` pins shard ↔ device — model
rank i holds weight columns [i*m_l, (i+1)*m_l) and (folded layout) parity
slot i — runs the per-device GEMMs locally, crosses the `model` axis with an
``all_gather`` of the T shard outputs (+ parity messages), and reruns the
exact single-device recovery (``core.decode_and_merge``) on every rank. A
dead device's contribution is what the erasure mask says it is: the rank's
column block and its folded parity slices, zeroed before decode.

This is the placement the paper measures (§6: each worker owns one weight
split; the master gathers T-of-(T+r) messages and locally subtracts), so the
multi-device tests validate real message loss rather than a simulated mask
on one device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.coded_layer import (CodedDenseSpec, decode_and_merge,
                                    merge_shards)
from repro.dist.sharding import batch_axes

__all__ = ["coded_matmul_shardmap"]


def coded_matmul_shardmap(
    x: jax.Array,
    w: jax.Array,
    w_cdc: jax.Array | None,
    spec: CodedDenseSpec,
    valid: jax.Array | None = None,
    *,
    mesh,
    axis: str = "model",
    valid_parity: jax.Array | None = None,
    acc_dtype=jnp.float32,
) -> jax.Array:
    """shard_map twin of ``core.coded_matmul`` (same signature + mesh).

    x: [..., k] activations; leading dim is additionally split over the
    pod/data axes when divisible. w: [k, m] with m = T * m_l; requires
    ``mesh.shape[axis] == T`` so shard i is physically model-rank i.
    Returns the merged [..., m], equal to ``x @ w`` under <= budget erasures.
    """
    code = spec.code
    T = code.n_shards
    if axis not in mesh.axis_names or mesh.shape[axis] != T:
        raise ValueError(
            f"mesh axis {axis!r} must exist with size T={T}, got "
            f"{dict(mesh.shape)}")
    k, m = w.shape
    if m % T:
        raise ValueError(f"output dim {m} not divisible by T={T}")

    coded = w_cdc is not None and code.n_parity > 0 and valid is not None
    folded = coded and spec.layout == "folded"
    if coded and valid_parity is None:
        valid_parity = valid

    # batch sharding of the activations over the non-model axes
    b_axes = tuple(a for a in batch_axes(mesh) if a != axis)
    n_b = 1
    for a in b_axes:
        n_b *= mesh.shape[a]
    if x.ndim < 2 or n_b <= 1 or x.shape[0] % n_b:
        b_axes = ()
    x_spec = P(*((b_axes if b_axes else None,)
                 + (None,) * (x.ndim - 1)))

    def local(xb, wb, cb, v, vp):
        # wb: [1, k, m_l] this rank's weight-column block
        y_i = xb @ wb[0]                                # [..., m_l]
        ys = jax.lax.all_gather(y_i, axis)              # [T, ..., m_l]
        if not coded:
            return merge_shards(ys)
        if folded:
            p_i = xb @ cb[0]                            # [..., r*w] my slot
            parity = jax.lax.all_gather(p_i, axis)      # [T, ..., r*w]
        else:
            # dedicated parity: the +r parity workers live off this mesh
            # axis; every rank re-derives their messages locally (cheap:
            # r/T of the data GEMM) instead of dedicating ranks.
            parity = jnp.einsum("...k,rkc->r...c", xb, cb,
                                preferred_element_type=xb.dtype)
        return decode_and_merge(ys, parity, spec, v, valid_parity=vp,
                                acc_dtype=acc_dtype)

    m_l = m // T
    w_blocked = jnp.moveaxis(w.reshape(k, T, m_l), 1, 0)  # [T, k, m_l]
    in_specs = [x_spec, P(axis, None, None)]
    args = [x, w_blocked]
    if coded:
        in_specs.append(P(axis, None, None) if folded else P(None, None,
                                                             None))
        args += [w_cdc, valid, valid_parity]
        in_specs += [P(None), P(None)]
        fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                           out_specs=x_spec, check_vma=False)
        return fn(*args)

    fn = jax.shard_map(lambda xb, wb: local(xb, wb, None, None, None),
                       mesh=mesh, in_specs=tuple(in_specs), out_specs=x_spec,
                       check_vma=False)
    return fn(x, w_blocked)

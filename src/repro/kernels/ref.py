"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Tolerance contract of the fused kernels, per dtype: TOL against the
# reference coded path and the plain GEMM, ORACLE_TOL against the oracles
# below. The kernels accumulate every GEMM in f32 and recover in-register;
# the reference path recovers in f32 too but rounds its shard GEMMs and
# the merged output to the activation dtype, so in bf16 the delta is
# bounded by those roundings, not the kernel's. The oracles mirror the
# kernels' f32 math exactly and are bit-identical in interpret mode; the
# looser oracle bound only allows for native-TPU rounding.
TOL = {
    "float32": dict(rtol=1e-4, atol=1e-4),    # vs reference / plain
    "bfloat16": dict(rtol=6e-2, atol=6e-2),
}
ORACLE_TOL = {
    "float32": dict(rtol=1e-5, atol=1e-5),    # vs these oracles
    "bfloat16": dict(rtol=2e-2, atol=2e-2),
}


def matmul_ref(x: jax.Array, w: jax.Array, out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or x.dtype
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(out_dtype)


def cdc_encode_ref(w_shards: jax.Array, gen: jax.Array) -> jax.Array:
    """[T, k, n] x [r, T] -> [r, k, n]."""
    acc = jnp.tensordot(gen.astype(jnp.float32),
                        w_shards.astype(jnp.float32), axes=[[1], [0]])
    return acc.astype(w_shards.dtype)


def cdc_decode_ref(y_shards: jax.Array, parity: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """r=1 recovery, paper Eq. 12. y: [T, m, n], parity: [m, n], valid: [T]."""
    vmask = valid.astype(jnp.float32)[:, None, None]
    y = y_shards.astype(jnp.float32) * vmask
    missing = parity.astype(jnp.float32) - y.sum(0)
    out = y + (1.0 - vmask) * missing[None]
    return out.astype(y_shards.dtype)


def fused_head_argmax_ref(x: jax.Array, w_shards: jax.Array,
                          parity_w: jax.Array, valid: jax.Array,
                          vocab: int, shard_width: int | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """Oracle for the fused coded head: shard GEMMs + Eq. 12 recovery +
    argmax over the merged logical vocabulary (shard columns at or past
    ``shard_width`` are padding). Returns (token, max_logit)."""
    y = jnp.einsum("bk,tkn->tbn", x.astype(jnp.float32),
                   w_shards.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    p = jnp.dot(x.astype(jnp.float32), parity_w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    rec = cdc_decode_ref(y, p, valid)             # [T, b, m_l]
    merged = jnp.moveaxis(rec, 0, -2)[..., :shard_width]   # [b, T, m_l]
    merged = merged.reshape(merged.shape[0], -1)[:, :vocab]
    return (jnp.argmax(merged, axis=-1).astype(jnp.int32),
            jnp.max(merged, axis=-1))


def _eq12_combine_ref(y: jax.Array, p: jax.Array, gen: jax.Array,
                      valid: jax.Array, esel: jax.Array,
                      coef: jax.Array) -> jax.Array:
    """Shared Eq. 12 tail of the in-body kernels: zero dead shards,
    rebuild the missing one from its selected parity equation, emit the
    merged [rows, T, m_l] layout. y: [T, rows, m_l] f32, p: [r, rows, m_l]
    f32, esel/coef: per-column plan from ``cdc_matmul.eq12_plan``."""
    vmask = valid[:, None, None]
    yz = jnp.where(vmask, y, 0.0)
    residual = p - jnp.tensordot(gen.astype(jnp.float32), yz,
                                 axes=[[1], [0]])          # [r, rows, m_l]
    onehot = jnp.arange(p.shape[0])[:, None] == esel[None, :]   # [r, m_l]
    pick = jnp.sum(jnp.where(onehot[:, None, :], residual, 0.0), axis=0)
    missing = pick * coef[None, :].astype(jnp.float32)
    out = jnp.where(vmask, yz, missing[None])
    return jnp.moveaxis(out, 0, 1)                         # [rows, T, m_l]


def cdc_coded_matmul_ref(x: jax.Array, w_shards: jax.Array,
                         parity_w: jax.Array, gen: jax.Array,
                         esel: jax.Array, coef: jax.Array,
                         valid: jax.Array, *, gamma: jax.Array | None = None,
                         eps: float = 1e-5, out_dtype=None) -> jax.Array:
    """Oracle for ``cdc_coded_matmul_pallas``: (rmsnorm?) + T shard GEMMs
    + r parity GEMMs + in-register Eq. 12 decode + merge, all f32.
    Returns merged [rows, T, m_l]."""
    xf = x.astype(jnp.float32)
    if gamma is not None:
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(var + eps) \
            * gamma.astype(jnp.float32)[None]
    y = jnp.einsum("bk,tkn->tbn", xf, w_shards.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    p = jnp.einsum("bk,rkn->rbn", xf, parity_w.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    out = _eq12_combine_ref(y, p, gen, valid, esel, coef)
    return out.astype(out_dtype or x.dtype)


def cdc_decode_merge_ref(ys: jax.Array, parity: jax.Array, gen: jax.Array,
                         esel: jax.Array, coef: jax.Array,
                         valid: jax.Array, out_dtype=None) -> jax.Array:
    """Oracle for ``cdc_decode_merge_pallas``: Eq. 12 decode + merge of
    already-computed shard outputs ys [T, rows, m_l] with UNFOLDED parity
    [r, rows, m_l]. Returns merged [rows, T, m_l]."""
    out = _eq12_combine_ref(ys.astype(jnp.float32),
                            parity.astype(jnp.float32), gen, valid, esel,
                            coef)
    return out.astype(out_dtype or ys.dtype)


def rmsnorm_ref(x: jax.Array, gamma: jax.Array, eps: float = 1e-6
                ) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * gamma.astype(jnp.float32)).astype(x.dtype)

"""CDC erasure codes over output-split GEMM shards (paper §5.2-5.3, §7).

The paper's code: for an output-split GEMM with T weight shards W_1..W_T
(split along the output dim), one parity shard W_cdc = sum_i W_i is computed
OFFLINE (input-independent). At runtime each shard output Y_i = X @ W_i and the
parity output Y_cdc = X @ W_cdc satisfy Y_cdc = sum_i Y_i, so a single missing
Y_m is recovered by a local subtraction (Eq. 6-7, Eq. 11-12).

Beyond the paper (§7 only sketches >1 failure): we generalize to r parity
shards with a real-valued MDS generator (Vandermonde on positive nodes, which
is totally positive => every square minor is nonsingular => any r erasures are
decodable). r=1 with the all-ones row is exactly the paper's sum code.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CodeSpec",
    "generator_matrix",
    "encode_weights",
    "encode_outputs",
    "decode_outputs",
    "max_decode_condition",
]


@dataclasses.dataclass(frozen=True)
class CodeSpec:
    """An (T + r, T) systematic erasure code over GEMM output shards.

    Attributes:
      n_shards: T, number of data shards (devices doing real output splits).
      n_parity: r, number of parity shards. r=1 => the paper's sum code.
      parity_dtype: dtype of the parity math and of the stored parity
        weights (fp32 recommended when weights and shard outputs are bf16:
        a bf16 parity weight rounds sum_i gen[j, i] W_i, and Eq. 12 then
        rebuilds a lost shard a few bf16 ulps off; see DESIGN.md §8).
    """

    n_shards: int
    n_parity: int = 1
    parity_dtype: jnp.dtype | None = jnp.float32

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if not (0 <= self.n_parity <= self.n_shards):
            raise ValueError(
                f"n_parity must be in [0, n_shards], got {self.n_parity}")

    @property
    def total_shards(self) -> int:
        return self.n_shards + self.n_parity

    @functools.cached_property
    def generator(self) -> np.ndarray:
        return generator_matrix(self.n_shards, self.n_parity)


#: Precision of the code's own f32 products (encode, residuals): a TPU's
#: default f32 dot is one bf16 pass, which would round the generator
#: coefficients and break exact Eq. 12 recovery.
EXACT = jax.lax.Precision.HIGHEST


def generator_matrix(n_shards: int, n_parity: int) -> np.ndarray:
    """(r, T) parity generator. Row j holds the combination coefficients.

    r=1: all-ones (the paper's W_cdc = sum_i W_i).
    r>1: Vandermonde rows c[j, i] = x_i**j with strictly positive increasing
    nodes x_i in (0, 2]. A Vandermonde matrix on positive increasing nodes is
    totally positive, so every e x e minor (any e parities x any e missing
    shards, e <= r) is nonsingular -- the code is MDS over the reals.
    """
    if n_parity == 0:
        return np.zeros((0, n_shards), dtype=np.float64)
    # Geometrically spaced nodes in [1/2, 2]: strictly positive & increasing
    # (total positivity => MDS), bounded powers (no fp32 under/overflow), and
    # a guaranteed multiplicative gap between nodes so every small decode
    # submatrix stays well-conditioned in fp32 for the r <= 4 regime.
    i = np.arange(n_shards, dtype=np.float64)
    nodes = 2.0 ** (2.0 * i / max(n_shards - 1, 1) - 1.0) \
        if n_shards > 1 else np.ones(1)
    powers = np.arange(n_parity, dtype=np.float64)[:, None]
    gen = nodes[None, :] ** powers  # row 0 is all-ones -> paper's sum code
    gen = gen / gen.max(axis=1, keepdims=True)  # row scale ~1 (row 0 intact)
    return gen


def max_decode_condition(spec: CodeSpec) -> float:
    """Worst-case condition number over all full-r erasure patterns.

    Checked at encode time (offline) so ill-conditioned (T, r) combos are
    rejected before deployment, mirroring the paper's offline weight prep.
    Exhaustive for small T, sampled otherwise.
    """
    import itertools

    if spec.n_parity == 0:
        return 1.0
    gen = spec.generator
    worst = 1.0
    combos = itertools.combinations(range(spec.n_shards), spec.n_parity)
    for n, missing in enumerate(combos):
        sub = gen[:, list(missing)]
        worst = max(worst, float(np.linalg.cond(sub)))
        if n > 2000:  # sampled bound for very large T
            break
    return worst


def encode_weights(w_shards: jax.Array, spec: CodeSpec) -> jax.Array:
    """Offline parity-weight construction (paper Eq. 7 / Eq. 11).

    Args:
      w_shards: [T, ..., m_shard] stacked weight shards (output dim last or
        anywhere -- coding acts only on the stacking axis).
      spec: code spec with spec.n_shards == T.

    Returns:
      [r, ..., m_shard] parity weights W_cdc[j] = sum_i gen[j, i] * W_i, in
      ``spec.parity_dtype`` (the shards' dtype when that is None).
    """
    if w_shards.shape[0] != spec.n_shards:
        raise ValueError(
            f"w_shards leading dim {w_shards.shape[0]} != T={spec.n_shards}")
    gen = jnp.asarray(spec.generator, dtype=spec.parity_dtype or w_shards.dtype)
    return jnp.tensordot(gen, w_shards.astype(gen.dtype), axes=[[1], [0]],
                         precision=EXACT)


def encode_outputs(y_shards: jax.Array, spec: CodeSpec) -> jax.Array:
    """Runtime parity of shard outputs (used by oracles/tests; in production
    the parity output comes from the parity *weights*, never from gathering
    all shard outputs -- that is the whole point of the code)."""
    dtype = spec.parity_dtype or y_shards.dtype
    gen = jnp.asarray(spec.generator, dtype=dtype)
    return jnp.tensordot(gen, y_shards.astype(dtype), axes=[[1], [0]],
                         precision=EXACT)


def decode_outputs(
    y_shards: jax.Array,
    parity: jax.Array,
    valid: jax.Array,
    spec: CodeSpec,
) -> jax.Array:
    """Recover erased shard outputs (paper Eq. 12 for r=1; MDS solve for r>1).

    Fully jit-compatible: static shapes, erasure pattern is a runtime mask.

    Args:
      y_shards: [T, ...] shard outputs; erased entries may hold garbage.
      parity:   [r, ...] parity outputs (from the parity weights).
      valid:    [T] bool; False marks an erased shard. At most r False.
      spec:     the code.

    Returns:
      [T, ...] outputs with erased shards reconstructed. Exact in exact
      arithmetic; see DESIGN.md §8 for float error bounds.
    """
    T, r = spec.n_shards, spec.n_parity
    if r == 0:
        return y_shards
    dtype = spec.parity_dtype or y_shards.dtype
    y = jnp.where(valid.reshape((T,) + (1,) * (y_shards.ndim - 1)),
                  y_shards.astype(dtype), 0)
    gen = jnp.asarray(spec.generator, dtype=dtype)  # [r, T]

    if r == 1:
        # Paper's fast path: y_miss = parity - sum_valid y (Eq. 12).
        missing_val = parity[0].astype(dtype) - jnp.sum(y, axis=0)
        rec = jnp.where(valid.reshape((T,) + (1,) * (y.ndim - 1)),
                        y, missing_val[None])
        return rec.astype(y_shards.dtype)

    # MDS path: solve an r x r system for up to r erased shards.
    # residual_j = parity_j - sum_{i valid} gen[j,i] y_i = sum_{i missing} gen[j,i] y_i
    residual = parity.astype(dtype) - jnp.tensordot(gen, y, axes=[[1], [0]],
                                                    precision=EXACT)
    # Static-shape selection of (up to) r missing indices; slots beyond the
    # actual erasure count are padded with valid indices whose equations are
    # replaced by identity rows (harmless).
    miss_score = jnp.where(valid, -1.0, 1.0)
    _, miss_idx = jax.lax.top_k(miss_score, r)  # [r] indices, erased first
    is_real = ~valid[miss_idx]  # [r] whether slot holds a true erasure
    # A[j, s] = gen[j, miss_idx[s]] for real slots; identity for padded slots.
    A = gen[:, miss_idx]  # [r, r]
    eye = jnp.eye(r, dtype=dtype)
    A = jnp.where(is_real[None, :], A, eye)
    rhs = jnp.where(is_real.reshape((r,) + (1,) * (residual.ndim - 1)),
                    residual, 0)
    flat_rhs = rhs.reshape(r, -1)
    sol = jnp.linalg.solve(A, flat_rhs).reshape(rhs.shape)  # [r, ...]
    # Scatter solutions back into the erased slots.
    rec = y
    upd = jnp.where(is_real.reshape((r,) + (1,) * (sol.ndim - 1)), sol, 0)
    rec = rec.at[miss_idx].add(upd)
    return rec.astype(y_shards.dtype)

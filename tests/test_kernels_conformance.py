"""Differential conformance for the fused in-body coded kernels.

The PR-7 acceptance pin: the fused Pallas coded GEMM + Eq. 12
decode-and-merge (``kernels.cdc_matmul`` via ``kernels.ops``) must agree
with THREE independent answers —

  fused kernel  ≡  ref.py oracle  ≡  core.coded_matmul  ≡  plain x @ w

— over T∈{2,4} × r∈{1,2}, both parity layouts, EVERY in-budget erasure
mask (including the 2-erasure dedicated masks that must take the exact
reference fallback), odd/non-tile-multiple shapes, and f32/bf16 with an
explicit per-dtype tolerance contract. Plus the structural guarantee the
kernels exist for: the fused path's jaxpr holds exactly ONE pallas_call
and ZERO outside-kernel dot_generals — per-shard GEMM outputs never
round-trip HBM.
"""
import itertools

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.coded_layer import (CodedDenseSpec, coded_matmul,
                                    decode_and_merge, make_parity_weights)
from repro.core.coding import CodeSpec
from repro.kernels import ops, ref
from repro.kernels.ref import ORACLE_TOL, TOL
from repro.models.common import rmsnorm

# Tolerance contract (TOL vs reference/plain, ORACLE_TOL vs the ref.py
# oracle): defined with the oracles in ``repro.kernels.ref``.

CASES = [(T, r, layout)
         for T in (2, 4) for r in (1, 2)
         for layout in ("folded", "dedicated")]
DTYPES = (jnp.float32, jnp.bfloat16)


def inbudget_masks(T: int, budget: int) -> list[tuple[bool, ...]]:
    """The full mask plus EVERY erasure subset within the code budget."""
    masks = [tuple([True] * T)]
    for f in range(1, budget + 1):
        for dead in itertools.combinations(range(T), f):
            m = [True] * T
            for d in dead:
                m[d] = False
            masks.append(tuple(m))
    return masks


def make_case(T, r, layout, dtype, *, rows=8, k=64, m=None, seed=0):
    spec = CodedDenseSpec(CodeSpec(T, r), layout=layout)
    if m is None:
        # folded parity slices need m_l % T == 0; dedicated takes odd m_l
        m = T * T * 2 if layout == "folded" else 28
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (rows, k)).astype(dtype)
    w = (jax.random.normal(kw, (k, m)) / np.sqrt(k)).astype(dtype)
    return spec, x, w, make_parity_weights(w, spec)


def _allclose(a, b, tol, msg):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               err_msg=msg, **tol)


# ------------------------------------------------- the core differential ----

@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("T,r,layout", CASES)
def test_fused_matches_oracle_reference_and_plain(T, r, layout, dtype):
    """fused ≡ oracle ≡ core.coded_matmul ≡ x@w under EVERY in-budget
    mask (single-erasure masks take the kernel; multi-erasure masks must
    take the bitwise-exact reference fallback)."""
    spec, x, w, wc = make_case(T, r, layout, dtype)
    dname = np.dtype(dtype).name
    plain = x.astype(jnp.float32) @ w.astype(jnp.float32)
    for mask in inbudget_masks(T, spec.max_device_failures):
        v = jnp.asarray(mask)
        dead = T - sum(mask)
        reference = coded_matmul(x, w, wc, spec, v)
        fused = ops.fused_coded_matmul(x, w, wc, spec, v)
        assert fused.dtype == x.dtype and fused.shape == reference.shape
        if dead > 1:
            # beyond the Eq. 12 regime: the EXACT reference path, bitwise
            np.testing.assert_array_equal(
                np.asarray(fused), np.asarray(reference),
                err_msg=f"{layout} T={T} r={r} mask={mask}: multi-erasure "
                        f"fallback must be the reference path verbatim")
            continue
        oracle = ops.fused_coded_matmul(x, w, wc, spec, v, use_pallas=False)
        ctx = f"{layout} T={T} r={r} {dname} mask={mask}"
        _allclose(fused, oracle, ORACLE_TOL[dname], f"{ctx}: vs oracle")
        _allclose(fused, reference, TOL[dname], f"{ctx}: vs reference")
        _allclose(fused, plain, TOL[dname], f"{ctx}: vs plain x@w")


@pytest.mark.parametrize("T,r,layout", CASES)
def test_odd_shapes_and_block_padding(T, r, layout):
    """Non-tile-multiple rows/k/m_l and block sizes that do NOT divide
    the problem: the wrapper's pad-and-slice must be invisible."""
    m = T * T * 3 if layout == "folded" else T * 7      # odd m_l (dedicated)
    spec, x, w, wc = make_case(T, r, layout, jnp.float32,
                               rows=5, k=33, m=m, seed=1)
    for mask in inbudget_masks(T, min(spec.max_device_failures, 1)):
        v = jnp.asarray(mask)
        reference = coded_matmul(x, w, wc, spec, v)
        for bm, bn in ((3, 5), (128, 128), (2, 1)):
            fused = ops.fused_coded_matmul(x, w, wc, spec, v, bm=bm, bn=bn)
            _allclose(fused, reference, TOL["float32"],
                      f"{layout} T={T} r={r} mask={mask} bm={bm} bn={bn}")


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("T,r,layout", CASES)
def test_decode_merge_matches_reference(T, r, layout, dtype):
    """The decode-and-merge tail (already-computed shard outputs, e.g.
    gathered by dist.collectives) — fused ≡ core.decode_and_merge under
    every in-budget mask, middle batch/seq dims included."""
    spec = CodedDenseSpec(CodeSpec(T, r), layout=layout)
    m_l = 2 * T if layout == "folded" else 7
    key = jax.random.PRNGKey(2)
    ky, kp = jax.random.split(key)
    pshape = ((T, 2, 3, r * (m_l // T)) if layout == "folded"
              else (r, 2, 3, m_l))
    ys = jax.random.normal(ky, (T, 2, 3, m_l)).astype(dtype)
    parity = jax.random.normal(kp, pshape).astype(dtype)
    dname = np.dtype(dtype).name
    for mask in inbudget_masks(T, spec.max_device_failures):
        v = jnp.asarray(mask)
        reference = decode_and_merge(ys, parity, spec, v)
        fused = ops.fused_decode_merge(ys, parity, spec, v)
        routed = decode_and_merge(ys, parity, spec, v, use_fused=True)
        if T - sum(mask) > 1:
            np.testing.assert_array_equal(np.asarray(fused),
                                          np.asarray(reference))
            continue
        ctx = f"{layout} T={T} r={r} {dname} mask={mask}"
        _allclose(fused, reference, TOL[dname], ctx)
        np.testing.assert_array_equal(
            np.asarray(routed), np.asarray(fused),
            err_msg=f"{ctx}: decode_and_merge(use_fused=True) must route "
                    f"to the fused op")


# ----------------------------------------------- property-based sweep ----

@settings(deadline=None, max_examples=12)
@given(data=st.data())
def test_fused_matches_reference_property(data):
    """Random geometry × values × in-budget mask: fused ≡ reference."""
    T = data.draw(st.sampled_from([2, 4]))
    r = data.draw(st.sampled_from([1, 2]))
    layout = data.draw(st.sampled_from(["folded", "dedicated"]))
    rows = data.draw(st.integers(1, 9))
    k = data.draw(st.integers(3, 48))
    m_l = data.draw(st.integers(1, 6)) * T  # folded-safe
    seed = data.draw(st.integers(0, 2 ** 16))
    spec, x, w, wc = make_case(T, r, layout, jnp.float32,
                               rows=rows, k=k, m=T * m_l, seed=seed)
    masks = inbudget_masks(T, min(spec.max_device_failures, 1))
    mask = masks[data.draw(st.integers(0, len(masks) - 1))]
    v = jnp.asarray(mask)
    fused = ops.fused_coded_matmul(x, w, wc, spec, v)
    _allclose(fused, coded_matmul(x, w, wc, spec, v), TOL["float32"],
              f"{layout} T={T} r={r} rows={rows} k={k} m_l={m_l} "
              f"mask={mask} seed={seed}")


# --------------------------------------------- rmsnorm fold (stretch) ----

@pytest.mark.parametrize("layout", ("folded", "dedicated"))
def test_rmsnorm_fold_matches_norm_then_matmul(layout):
    """gamma-folding: fused(norm+GEMM+decode+merge) ≡ rmsnorm then the
    reference coded matmul, fault-free and under one erasure."""
    T, r = 4, 2
    spec, x, w, wc = make_case(T, r, layout, jnp.float32, seed=3)
    g = jax.random.normal(jax.random.PRNGKey(4), (x.shape[-1],)) * 0.1 + 1.0
    for mask in [(True,) * T, (True, False, True, True)]:
        v = jnp.asarray(mask)
        xn = rmsnorm({"g": g}, x)                   # models' eps=1e-5
        reference = coded_matmul(xn, w, wc, spec, v)
        fused = ops.fused_coded_matmul(x, w, wc, spec, v, gamma=g, eps=1e-5)
        _allclose(fused, reference, TOL["float32"],
                  f"{layout} mask={mask}: rmsnorm fold")


# ------------------------------------------- erasure-limit guards ----

def test_fused_head_argmax_rejects_multi_erasure():
    """Satellite: the sum-parity fused head recovers <=1 shard; a
    concrete 2-dead mask must raise loudly, never decode garbage."""
    x = jnp.ones((2, 8))
    w_shards = jnp.ones((4, 8, 4))
    with pytest.raises(ValueError, match="at most 1 erased"):
        ops.fused_head_argmax(x, w_shards, w_shards.sum(0),
                              jnp.asarray([False, True, False, True]),
                              vocab=15)


def test_cdc_decode_rejects_multi_erasure():
    with pytest.raises(ValueError, match="at most 1 erased"):
        ops.cdc_decode(jnp.ones((4, 8, 8)), jnp.ones((8, 8)),
                       jnp.asarray([False, False, True, True]))


def test_multi_erasure_matmul_falls_back_not_raises():
    """The in-body op DOES have an exact fallback (full MDS reference):
    an in-budget 2-erasure dedicated mask returns the reference answer."""
    spec, x, w, wc = make_case(4, 2, "dedicated", jnp.float32, seed=5)
    v = jnp.asarray([True, False, False, True])
    out = ops.fused_coded_matmul(x, w, wc, spec, v)
    _allclose(out, x.astype(jnp.float32) @ w.astype(jnp.float32),
              TOL["float32"], "2-erasure recovery through the fallback")


# -------------------------------------------- policy + structure pins ----

def test_auto_policy_is_reference_off_tpu():
    """use_fused='auto' must resolve to the plain-jnp reference path off
    TPU (bitwise) — interpret mode is opt-in via use_fused=True."""
    spec, x, w, wc = make_case(4, 2, "folded", jnp.float32, seed=6)
    v = jnp.asarray([True, False, True, True])
    auto = coded_matmul(x, w, wc, spec, v, use_fused="auto")
    reference = coded_matmul(x, w, wc, spec, v)
    if jax.default_backend() != "tpu":
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(reference))
    else:
        _allclose(auto, reference, TOL["float32"], "auto on TPU")


def _count_primitives(closed_jaxpr):
    """(n_pallas_call, n_dot_general_outside_kernels) over the whole
    jaxpr tree — dot_generals INSIDE a pallas_call body are the in-VMEM
    kernel math and don't count as an HBM round-trip."""
    counts = {"pallas_call": 0, "dot_general": 0}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in counts:
                counts[name] += 1
            if name == "pallas_call":
                continue                      # kernel-internal math
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, jex_core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex_core.Jaxpr):
                        walk(sub)

    walk(closed_jaxpr.jaxpr)
    return counts["pallas_call"], counts["dot_general"]


def test_fused_path_has_no_pershard_hbm_roundtrip():
    """Structural acceptance pin: the fused coded matmul lowers to
    exactly ONE pallas_call with ZERO GEMMs outside it — shard outputs
    and parity outputs live only in kernel VMEM, the only HBM write is
    the merged activation."""
    spec, x, w, wc = make_case(4, 2, "folded", jnp.float32, seed=7)
    v = jnp.asarray([True, False, True, True])
    jaxpr = jax.make_jaxpr(
        lambda xx: ops.fused_coded_matmul(xx, w, wc, spec, v))(x)
    n_pallas, n_dots = _count_primitives(jaxpr)
    assert n_pallas == 1, f"expected one fused kernel, got {n_pallas}"
    assert n_dots == 0, (f"{n_dots} dot_general(s) outside the kernel — "
                         f"per-shard outputs are round-tripping HBM")
    # the reference path, for contrast, runs its GEMMs as plain XLA dots
    jaxpr_ref = jax.make_jaxpr(
        lambda xx: coded_matmul(xx, w, wc, spec, v))(x)
    _, ref_dots = _count_primitives(jaxpr_ref)
    assert ref_dots >= 1


def test_merge_is_free_reshape():
    """The kernel writes [rows, T, m_l] in merge order: flattening the
    last two axes IS the merged activation (column t*m_l + c)."""
    spec, x, w, wc = make_case(4, 2, "folded", jnp.float32, seed=8)
    v = jnp.ones(4, bool)
    fused = ops.fused_coded_matmul(x, w, wc, spec, v)
    T = 4
    m = w.shape[1]
    m_l = m // T
    per_shard = np.asarray(fused).reshape(x.shape[0], T, m_l)
    plain = np.asarray(x @ w).reshape(x.shape[0], T, m_l)
    np.testing.assert_allclose(per_shard, plain, rtol=1e-4, atol=1e-4)

"""Property tests for the dist layer: the coded GEMM is a drop-in GEMM.

Two tiers:
  * in-process (1 device): ``core.coded_matmul`` == plain ``x @ w`` across
    random shapes, T in {2, 4}, r in {1, 2}, both layouts, and every
    erasure mask within the layout's budget;
  * subprocess (8 fake devices, ``multidev``): the same property loop with
    the explicit shard_map path in the triangle —
    ``coded_matmul_shardmap`` == ``core.coded_matmul`` == ``x @ w``.

Uses real hypothesis when installed, else the deterministic shim.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback keeps the suite collecting everywhere
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import CodedDenseSpec, CodeSpec, coded_matmul, \
    make_parity_weights

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(b, k, mult, T, r, layout, n_dead, perm):
    """Build one random coded-GEMM case with <= budget erasures."""
    code = CodeSpec(T, r)
    spec = CodedDenseSpec(code, layout=layout)
    m = T * T * mult * 2  # folded slices need m % T^2 == 0
    kx, kw = jax.random.split(jax.random.PRNGKey(b * 1000 + k))
    x = jax.random.normal(kx, (b, k))
    w = jax.random.normal(kw, (k, m)) / max(k, 1) ** 0.5
    w_cdc = make_parity_weights(w, spec)
    dead = perm[:min(n_dead, spec.max_device_failures)]
    valid = jnp.ones(T, bool)
    for d in dead:
        valid = valid.at[d].set(False)
    return spec, x, w, w_cdc, valid


@settings(max_examples=16, deadline=None)
@given(b=st.integers(1, 5), k=st.integers(1, 40), mult=st.integers(1, 3),
       T=st.sampled_from([2, 4]), r=st.sampled_from([1, 2]),
       layout=st.sampled_from(["folded", "dedicated"]), data=st.data())
def test_coded_matmul_is_a_gemm_under_erasures(b, k, mult, T, r, layout,
                                               data):
    perm = data.draw(st.permutations(list(range(T))))
    n_dead = data.draw(st.integers(0, r))
    spec, x, w, w_cdc, valid = _case(b, k, mult, T, r, layout, n_dead, perm)
    got = coded_matmul(x, w, w_cdc, spec, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.multidev
def test_shardmap_triple_equivalence_properties():
    """Subprocess (8 fake devices): shard_map == logical == plain GEMM for
    random shapes, T in {2,4}, r in {1,2}, masks within budget."""
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, {tests!r})
        try:
            from hypothesis import given, settings, strategies as st
        except ImportError:
            from _hypothesis_fallback import given, settings, \\
                strategies as st
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import CodedDenseSpec, CodeSpec, coded_matmul, \\
            make_parity_weights
        from repro.dist.collectives import coded_matmul_shardmap
        from repro.launch.mesh import make_mesh

        assert len(jax.devices()) == 8
        MESHES = {{2: make_mesh((4, 2), ("data", "model")),
                   4: make_mesh((2, 4), ("data", "model"))}}

        @settings(max_examples=10, deadline=None)
        @given(b=st.integers(1, 5), k=st.integers(1, 40),
               mult=st.integers(1, 2), T=st.sampled_from([2, 4]),
               r=st.sampled_from([1, 2]),
               layout=st.sampled_from(["folded", "dedicated"]),
               data=st.data())
        def prop(b, k, mult, T, r, layout, data):
            code = CodeSpec(T, r)
            spec = CodedDenseSpec(code, layout=layout)
            m = T * T * mult * 2
            kx, kw = jax.random.split(jax.random.PRNGKey(b * 1000 + k))
            x = jax.random.normal(kx, (b, k))
            w = jax.random.normal(kw, (k, m)) / max(k, 1) ** 0.5
            w_cdc = make_parity_weights(w, spec)
            perm = data.draw(st.permutations(list(range(T))))
            n_dead = data.draw(st.integers(0, r))
            valid = jnp.ones(T, bool)
            for d in perm[:min(n_dead, spec.max_device_failures)]:
                valid = valid.at[d].set(False)
            got = coded_matmul_shardmap(x, w, w_cdc, spec, valid,
                                        mesh=MESHES[T])
            logical = coded_matmul(x, w, w_cdc, spec, valid)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(logical),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                       rtol=2e-3, atol=2e-3)

        prop()
        print("OK")
    """).format(tests=os.path.join(REPO, "tests"))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout

"""Pipeline-parallelism correctness (subprocess, 8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_gpipe_over_pod_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.dist.pipeline import pipeline_apply

        mesh = make_mesh((4, 2), ("pod", "data"))
        L, D = 8, 32
        keys = jax.random.split(jax.random.PRNGKey(0), L)
        params = {"w": jnp.stack([
            jax.random.normal(k, (D, D)) / D ** 0.5 for k in keys]),
            "b": jnp.zeros((L, D))}

        def layer(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D))

        # sequential reference
        h = x
        for i in range(L):
            h = layer(jax.tree.map(lambda a: a[i], params), h)

        got = pipeline_apply(layer, params, x, mesh=mesh, n_microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out

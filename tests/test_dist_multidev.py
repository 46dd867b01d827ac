"""Multi-device tests (subprocess: 8 fake host devices).

XLA locks the device count at first jax init, so these run in fresh
interpreter processes with XLA_FLAGS set. Validates that GSPMD sharding of
the coded model is semantics-preserving: the sharded coded forward equals
the single-device forward, with and without erasures.
"""
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
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_coded_forward_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, smoke_config
        from repro.models import TPCtx, build
        from repro.dist.sharding import param_shardings, batch_spec

        assert len(jax.devices()) == 8
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = smoke_config(get_arch("granite-3-8b"))

        # single-device reference (same logical T=4 coded math)
        ctx0 = TPCtx(tp=4, mode="coded", code_r=2, moe_capacity=0)
        m0 = build(cfg, ctx0)
        params = m0.init(jax.random.PRNGKey(0))
        batch = m0.dummy_batch(jax.random.PRNGKey(1), 4, 8)
        valid = jnp.ones(4, bool)
        ref = m0.forward(params, batch, valid)

        # sharded on the mesh
        ctx = TPCtx(tp=4, mode="coded", code_r=2, mesh=mesh, moe_capacity=0)
        m = build(cfg, ctx)
        ps = param_shardings(params, mesh)
        params_sh = jax.device_put(params, ps)
        batch_sh = jax.device_put(
            batch, {"tokens": NamedSharding(mesh, batch_spec(mesh))})
        fwd = jax.jit(lambda p, b, v: m.forward(p, b, v))
        got = fwd(params_sh, batch_sh, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

        # erasure under sharding: still equals fault-free reference
        dead = valid.at[1].set(False)
        got_dead = fwd(params_sh, batch_sh, dead)
        np.testing.assert_allclose(np.asarray(got_dead), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)
        print("OK")
    """)
    assert "OK" in out


def test_plain_tp_sharded_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding
        from repro.configs import get_arch, smoke_config
        from repro.models import TPCtx, build
        from repro.dist.sharding import param_shardings, batch_spec

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = smoke_config(get_arch("qwen2-moe-a2.7b"))
        ctx0 = TPCtx(tp=4, moe_capacity=0)
        m0 = build(cfg, ctx0)
        params = m0.init(jax.random.PRNGKey(0))
        batch = m0.dummy_batch(jax.random.PRNGKey(1), 4, 8)
        ref = m0.forward(params, batch)

        ctx = TPCtx(tp=4, mesh=mesh, moe_capacity=0)
        m = build(cfg, ctx)
        params_sh = jax.device_put(params, param_shardings(params, mesh))
        batch_sh = jax.device_put(
            batch, {"tokens": NamedSharding(mesh, batch_spec(mesh))})
        got = jax.jit(lambda p, b: m.forward(p, b))(params_sh, batch_sh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_multipod_mesh_and_elastic_restore():
    """(pod,data,model) mesh accepts the shardings; a checkpoint saved from
    the 8-device mesh restores onto a 1-device process (elastic re-mesh)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from repro.launch.mesh import make_mesh
        from repro.configs import get_arch, smoke_config
        from repro.models import TPCtx, build
        from repro.dist.sharding import param_shardings
        from repro.ckpt import save

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = smoke_config(get_arch("h2o-danube-1.8b"))
        ctx = TPCtx(tp=2, mesh=mesh)
        m = build(cfg, ctx)
        params = m.init(jax.random.PRNGKey(0))
        params_sh = jax.device_put(params, param_shardings(params, mesh))
        d = tempfile.mkdtemp()
        save(params_sh, d, 3)
        print("SAVED", d)
    """)
    assert "SAVED" in out
    ckpt_dir = out.strip().split()[-1]
    out2 = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch, smoke_config
        from repro.models import TPCtx, build
        from repro.ckpt import restore

        cfg = smoke_config(get_arch("h2o-danube-1.8b"))
        m = build(cfg, TPCtx(tp=2))
        tmpl = m.init(jax.random.PRNGKey(42))
        out = restore(tmpl, {ckpt_dir!r}, 3)
        # restored values differ from the fresh init => real load happened
        a = np.asarray(jax.tree.leaves(out)[0], np.float32)
        b = np.asarray(jax.tree.leaves(tmpl)[0], np.float32)
        assert not np.allclose(a, b)
        print("OK")
    """)
    assert "OK" in out2


def test_shardmap_coded_matmul_explicit_placement():
    """Erasure sweep over the shard_map coded GEMM (explicit per-device
    placement): EVERY single dead shard index for T=4, r=2 recovers and
    matches both the plain GEMM and the GSPMD/logical path. The masks are
    driven through the shard-health controller, which also maps each
    erasure onto the real mesh devices holding that shard."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import CodedDenseSpec, CodeSpec, coded_matmul, \\
            make_parity_weights
        from repro.dist.collectives import coded_matmul_shardmap
        from repro.runtime.health import ShardHealthController, erasure, \\
            recovery

        mesh = make_mesh((2, 4), ("data", "model"))
        T = 4
        spec = CodedDenseSpec(CodeSpec(T, 2))
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (8, 64))
        w = jax.random.normal(kw, (64, T * T * 8)) / 8.0
        w_cdc = make_parity_weights(w, spec)
        ref = x @ w
        ctrl = ShardHealthController(T, spec.max_device_failures)
        for dead in (None,) + tuple(range(T)):
            if dead is not None:
                ctrl.apply(erasure(0.0, dead))
            valid = jnp.asarray(ctrl.mask)
            # logical shard <-> physical device placement is real: the
            # controller names the mesh devices the erasure hit
            dmask = ctrl.device_mask(mesh)
            assert dmask.shape == mesh.devices.shape
            assert len(ctrl.dead_devices(mesh)) == \\
                (0 if dead is None else 2)  # one per data replica
            got = coded_matmul_shardmap(x, w, w_cdc, spec, valid, mesh=mesh)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-3, atol=2e-3)
            logical = coded_matmul(x, w, w_cdc, spec, valid)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(logical),
                                       rtol=1e-4, atol=1e-4)
            if dead is not None:
                ctrl.apply(recovery(1.0, dead))
        print("OK")
    """)
    assert "OK" in out

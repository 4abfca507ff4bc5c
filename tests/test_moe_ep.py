"""EP (shard_map) MoE dispatch vs the dense_scatter reference, on an
8-host-device mesh (subprocess keeps the device flag out of this
session). Capacity is set non-binding so the two dispatches must agree
exactly."""
import os
import subprocess
import sys
import textwrap


def test_ep_moe_matches_dense_scatter():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import MoEConfig
        from repro.models import moe as MO

        cfg = MoEConfig(n_experts=8, top_k=2, d_expert=32,
                        capacity_factor=8.0, dispatch="dense_scatter")
        key = jax.random.PRNGKey(0)
        p = MO.moe_init(key, 16, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))

        ref, m_ref = MO.moe_apply(p, x, cfg, compute_dtype=jnp.float32)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        from jax.sharding import NamedSharding
        S = lambda *spec: NamedSharding(mesh, P(*spec))
        with jax.set_mesh(mesh):
            ep = jax.jit(lambda p, x: MO.moe_apply_ep(
                p, x, cfg, compute_dtype=jnp.float32)[0],
                in_shardings=(S(), S("data", None)),
                out_shardings=S("data", None))(p, x)
        err = float(jnp.max(jnp.abs(ref - ep)))
        assert err < 1e-4, err
        # gradient parity through the EP region
        def loss_ep(p):
            with jax.set_mesh(mesh):
                out = jax.jit(lambda p: MO.moe_apply_ep(
                    p, x, cfg, compute_dtype=jnp.float32)[0])(p)
            return jnp.sum(out ** 2)
        def loss_ref(p):
            return jnp.sum(MO.moe_apply(p, x, cfg,
                                        compute_dtype=jnp.float32)[0] ** 2)
        with jax.set_mesh(mesh):
            g_ep = jax.jit(jax.grad(lambda p: jnp.sum(MO.moe_apply_ep(
                p, x, cfg, compute_dtype=jnp.float32)[0] ** 2)))(p)
        g_ref = jax.grad(loss_ref)(p)
        for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "PYTHONPATH": "src"}, cwd=repo)
    assert "OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])

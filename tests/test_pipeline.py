"""GPipe pipeline-parallel tests (subprocess: needs >1 host device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_with_devices(n, code):
    env = dict(os.environ)
    # virtual host devices: the child stays off any accelerator the parent
    # may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.parametrize("mesh_shape,n_dev", [("(2, 2, 2)", 8), ("(4, 2)", 8)])
def test_gpipe_matches_sequential(mesh_shape, n_dev):
    axes = "('pod', 'data', 'model')" if "2, 2, 2" in mesh_shape else "('pod', 'data')"
    out = _run_with_devices(n_dev, f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distribution.pipeline import gpipe
        mesh = make_mesh({mesh_shape}, {axes})
        S = mesh.shape['pod']
        D, L, MB, NM = 16, 8, 4, 6
        w = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.3
        stage_w = w.reshape(S, L // S, D, D)
        def stage_fn(pw, x):
            def body(h, wi):
                return jnp.tanh(h @ wi), None
            return jax.lax.scan(body, x, pw)[0]
        x = jax.random.normal(jax.random.key(1), (NM, MB, D))
        with mesh:
            y = jax.jit(lambda p, x: gpipe(stage_fn, p, x, mesh=mesh, n_micro=NM))(stage_w, x)
        h = x
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(h), rtol=1e-5, atol=1e-5)
        print('OK')
    """)
    assert "OK" in out


def test_gpipe_single_stage_fallback():
    out = _run_with_devices(2, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distribution.pipeline import gpipe
        mesh = make_mesh((1, 2), ('pod', 'data'))
        D, MB, NM = 8, 4, 3
        w = jax.random.normal(jax.random.key(0), (1, 2, D, D)) * 0.3
        def stage_fn(pw, x):
            return jax.lax.scan(lambda h, wi: (jnp.tanh(h @ wi), None), x, pw)[0]
        x = jax.random.normal(jax.random.key(1), (NM, MB, D))
        with mesh:
            y = gpipe(stage_fn, w, x, mesh=mesh, n_micro=NM)
        h = x
        for i in range(2):
            h = jnp.tanh(h @ w[0, i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(h), rtol=1e-5, atol=1e-5)
        print('OK')
    """)
    assert "OK" in out

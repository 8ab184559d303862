"""Distribution-layer tests: collective sizing cross-checks, EP MoE parity
on multi-device meshes (subprocess), elastic checkpoint re-shard."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.distribution import collectives as co

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


# ---------------------------------------------------------------------------
# analytic collective model
# ---------------------------------------------------------------------------
def test_ring_identities():
    n, b = 16, 1e9
    assert co.ring_all_reduce(b, n) == co.all_gather(b, n) + co.reduce_scatter(b, n)
    assert co.ring_all_reduce(b, 1) == 0.0
    assert co.all_to_all(b, n) < co.all_gather(b, n)


def test_collective_model_matches_hlo_order_of_magnitude():
    """Analyzer's all-reduce total for mistral prefill ~ analytic TP model.

    CPU lowering upcasts bf16 collectives to f32 (documented 2x), and the
    analyzer counts operand bytes (not ring wire bytes) — assert within a
    factor of 4 to pin the structure, not the constant.
    """
    art = os.path.join(
        os.path.dirname(__file__), "..",
        "artifacts/dryrun/pod16x16/mistral-large-123b__prefill_32k.json",
    )
    if not os.path.exists(art):
        pytest.skip("dry-run artifact not present")
    cell = json.load(open(art))
    if cell.get("status") != "ok" or cell.get("sp"):
        pytest.skip("cell not comparable")
    got = cell["per_device"]["collective_bytes"].get("all-reduce", 0.0)
    # tokens_local = global_batch/dp * seq; bf16 activations
    act = (32 // 16) * 32768 * 12288 * 2
    model = co.CollectiveModel(
        n_layers=88, d_model=12288, d_ff=28672,
        params_bytes=2 * 123e9, tp=16, dp=16, act_bytes_per_layer=act,
    )
    want = model.tp_all_reduce_bytes() / 2  # analyzer counts operand, not 2x ring
    assert want / 4 <= got <= want * 4, (got, want)


# ---------------------------------------------------------------------------
# EP MoE parity on real multi-device meshes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mesh_shape,n_dev", [("(2, 4)", 8), ("(1, 8)", 8)])
def test_moe_ep_matches_dispatch_multidevice(mesh_shape, n_dev):
    out = _run_with_devices(n_dev, f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced
        from repro.models import bundle, moe as moe_mod
        from repro.distribution import sharding as shd
        cfg = reduced(get_config('mixtral-8x7b'), capacity_factor=8.0)
        mb = bundle(cfg)
        params = mb.init(jax.random.key(0))
        batch = {{'tokens': jax.random.randint(jax.random.key(1), (4, 16), 1, 255)}}
        mesh = make_mesh({mesh_shape}, ('data', 'model'))
        with shd.use_mesh(mesh, fsdp=True):
            moe_mod.set_moe_impl('dispatch')
            l1, _ = jax.jit(mb.loss_fn)(params, batch)
            moe_mod.set_moe_impl('alltoall')
            l2, _ = jax.jit(mb.loss_fn)(params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-2)
        print('OK', float(l1), float(l2))
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# fault tolerance: elastic re-shard (save on N devices, restore on M)
# ---------------------------------------------------------------------------
def test_checkpoint_elastic_reshard(tmp_path):
    ck = str(tmp_path / "ck")
    save_code = f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced
        from repro.models import bundle
        from repro.distribution import sharding as shd
        from repro.training import optimizer as opt
        from repro.training.checkpoint import CheckpointManager
        cfg = reduced(get_config('smollm-135m'))
        mb = bundle(cfg)
        mesh = make_mesh((4,), ('data',))
        with shd.use_mesh(mesh, fsdp=True):
            params = mb.init(jax.random.key(7))
            ocfg = opt.AdamWConfig()
            state = opt.init(params, ocfg)
            pn = shd.named(shd.param_specs(params, mesh, True), mesh)
            params = jax.tree.map(jax.device_put, params, pn)
            CheckpointManager('{ck}').save(3, params, state, blocking=True)
        print('saved', float(jax.tree.leaves(params)[0].sum()))
    """
    out1 = _run_with_devices(4, save_code)
    ref = float(out1.split("saved")[1].strip())

    restore_code = f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced
        from repro.models import bundle
        from repro.distribution import sharding as shd
        from repro.training import optimizer as opt
        from repro.training.checkpoint import CheckpointManager
        cfg = reduced(get_config('smollm-135m'))
        mb = bundle(cfg)
        mesh = make_mesh((3, 2), ('data', 'model'))  # DIFFERENT topology
        with shd.use_mesh(mesh, fsdp=True):
            tmpl_p = mb.param_shapes()
            ocfg = opt.AdamWConfig()
            tmpl_o = jax.eval_shape(lambda p: opt.init(p, ocfg), tmpl_p)
            pn = shd.named(shd.param_specs(tmpl_p, mesh, True), mesh)
            on = shd.named(shd.opt_state_specs(tmpl_p, tmpl_o, mesh, True), mesh)
            mgr = CheckpointManager('{ck}')
            assert mgr.latest_step() == 3
            params, state = mgr.restore(3, tmpl_p, tmpl_o, shardings=(pn, on))
        leaf = jax.tree.leaves(params)[0]
        assert len(leaf.sharding.device_set) >= 1
        print('restored', float(leaf.sum()))
    """
    out2 = _run_with_devices(6, restore_code)
    got = float(out2.split("restored")[1].strip())
    np.testing.assert_allclose(got, ref, rtol=1e-2)

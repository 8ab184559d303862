"""CPU rehearsal of chip_smoke.py: its phases at reduced widths, a 64-GPU
fleet and Pallas in interpret mode.  The device phase is left out: off the
chip it must fail, and ``test_main_refuses_cpu`` checks that it does."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config, reduced

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _small(arch: str):
    return reduced(get_config(arch), dtype="bfloat16")


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "no TPU" in str(e.value.code)
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_control_plane_rehearsal():
    line = chip_smoke.phase_control_plane(n_gpus=64, fabric_mode="on")
    assert "fabric == scalar placements" in line and "sweep on cpu" in line


def test_served_smollm_rehearsal():
    line = chip_smoke.phase_served_smollm(_small("smollm-135m"), interpret=True)
    assert "first token" in line


def test_served_glm_rehearsal():
    line = chip_smoke.phase_served_glm(_small("chatglm3-6b"), interpret=True,
                                       max_len=512)
    assert "4 requests complete" in line


def test_kernels_rehearsal():
    line = chip_smoke.phase_kernels(
        [_small("smollm-135m"), _small("chatglm3-6b")], _small("chatglm3-6b"),
        _small("zamba2-1.2b"), seq=128, cache_len=256, interpret=True,
    )
    assert line.count("max err") == 6


def test_replicas_rehearsal_four_devices():
    """One replica per device on four virtual host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax
        import chip_smoke
        from repro.configs import get_config, reduced
        cfg = reduced(get_config("smollm-135m"), dtype="bfloat16")
        print(chip_smoke.phase_replicas(cfg, jax.devices(), max_len=256))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "x4 on distinct devices" in out.stdout
    for i in range(4):
        assert f"->device {i}" in out.stdout


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """An outside JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
    cache is the checkout's fixed .jax_cache/."""
    import jax

    from repro.launch import compile_cache

    set_to = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: set_to.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert set_to == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert set_to == []

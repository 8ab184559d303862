"""The trace reduction on a small CPU trace recorded here."""
import time

import jax
import jax.numpy as jnp

import bench_testkit  # noqa: F401  (puts bench/ on the path)
import trace_reduce


def _cpu_client(name: str) -> bool:
    return name.startswith("tf_XLAPjRtCpuClient") or name.startswith("tf_XLACpu")


def test_busy_time_and_named_idle_gaps(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(4):
        with jax.profiler.TraceAnnotation("pump"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace_reduce.latest_xplane(str(tmp_path))
    red = trace_reduce.reduce_trace(
        path, ("pump", "wait"), device_plane=lambda n: n == "/host:CPU",
        ops_line=_cpu_client, modules_line=lambda n: False)
    assert red is not None and red["n_devices"] == 1
    assert 0 < red["busy_s"] < 0.2
    assert red["device_ops"] and all(s > 0 for _, s in red["device_ops"])
    assert red["idle_gaps"][0][0] == "wait"
    assert red["idle_gaps"][0][1] > 0.015


def test_no_device_plane_reads_nothing(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    assert trace_reduce.reduce_trace(trace_reduce.latest_xplane(str(tmp_path)),
                                     ("pump",)) is None


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]

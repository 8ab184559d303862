"""The placement cell's readers on hand-made records: what each reads, and
that each reads nothing where its layer left nothing."""
import pytest

import bench_testkit as kit


def _reader(name):
    return kit.harness.load_module(kit.ROOT / "bench" / "metrics" / f"{name}.py")


def _rec(**kw):
    rec = {"kind": "placement", "place_s": [0.001 * i for i in range(1, 101)],
           "deploy_s": [0.002, 0.001, 0.003], "compact_s": [4.0, 2.0], "trace": None}
    rec.update(kw)
    return rec


@pytest.mark.parametrize("name,want", [
    ("place_p95_ms", 95.05), ("deploy_ms", 2.0), ("compact_ms", 3000.0)])
def test_placement_readers(name, want):
    assert _reader(name).read(_rec()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["place_p95_ms", "deploy_ms", "compact_ms",
                                  "fabric_sweep_ms"])
def test_placement_readers_read_nothing_of_a_served_run(name):
    assert _reader(name).read({"kind": "served", "trace": None}) is None


def test_fabric_sweep_is_device_time_per_execution():
    trace = {"programs": {"jit__feasible_kernel": 4e-6, "jit__score_kernel": 2e-6,
                          "jit_other": 1.0},
             "program_runs": {"jit__feasible_kernel": 2, "jit__score_kernel": 1,
                              "jit_other": 5}}
    assert _reader("fabric_sweep_ms").read(_rec(trace=trace)) == pytest.approx(0.002)
    trace = {"programs": {"jit_other": 1.0}, "program_runs": {"jit_other": 5}}
    assert _reader("fabric_sweep_ms").read(_rec(trace=trace)) is None

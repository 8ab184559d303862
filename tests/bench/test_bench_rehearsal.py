"""CPU rehearsal of whole benchmark runs at a tiny size: the result line's
keys, repeatability of the placement cell, and the command's refusal to
run anywhere but on a TPU."""
import json
import os
import subprocess
import sys

import pytest

import bench_testkit as kit


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return kit.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", [kit.SERVED, kit.PLACED])
@pytest.mark.parametrize("trace", [False, True])
def test_result_has_contract_keys(tiny, cell, trace, monkeypatch):
    root, bench = tiny
    monkeypatch.setattr(kit.harness, "TRACE_FROM_S", 0.5)
    monkeypatch.setattr(kit.harness, "TRACE_TO_S", 1.5)
    res = kit.run(root, bench, cell, trace=trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    specs = bench["per_layer"] if trace else bench["end_to_end"]
    want = {m["name"] for m in specs if cell in m.get("workloads", [cell])}
    got = set(res["metrics"])
    if trace:  # device-trace readers find nothing to read on the CPU
        want = {m["name"] for m in specs if cell in m.get("workloads", [cell])
                and m["source"] != "device_trace"}
    assert want <= got and got <= {m["name"] for m in specs}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(res)


def test_gpus_used_repeats_exactly_per_seed(tiny):
    root, bench = tiny
    a = kit.run(root, bench, kit.PLACED, seed=2**40 + 3)
    b = kit.run(root, bench, kit.PLACED, seed=2**40 + 3)
    assert a["metrics"]["gpus_used"] == b["metrics"]["gpus_used"]
    assert a["attempted"] == b["attempted"]


def test_command_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(kit.ROOT / "bench" / "run.py"), "--workload",
         "smollm-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=kit.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

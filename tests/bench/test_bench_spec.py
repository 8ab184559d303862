"""BENCHMARK.json keeps to the benchmark's contract: keys, names, units,
bounds, files, and every metric and cell found by its name."""
import json
import re

import bench_testkit as kit

SPEC = json.loads((kit.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (kit.ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (kit.ROOT / SPEC["command"][1]).is_file()


def test_configs_and_cells():
    names = {c["name"] for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert names == used
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (kit.ROOT / c["file"]).is_file()
        cfg = json.loads((kit.ROOT / c["file"]).read_text())
        assert (kit.ROOT / cfg["reference"]).is_file()
        assert (kit.ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (kit.ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(pairs) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert (kit.ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for w in cells:  # every cell reports setup_s, one more end-to-end, one per-layer
        assert sum(w in m.get("workloads", cells) for m in SPEC["end_to_end"]) >= 2
        assert any(w in m.get("workloads", cells) for m in SPEC["per_layer"])

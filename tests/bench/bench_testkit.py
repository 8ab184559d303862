"""Tiny stand-ins for the benchmark's cells, for runs on the CPU.

A tiny root holds links to the real drivers, metric readers and
configuration references, tiny configuration files and tiny traffic mixes,
so that ``harness.run`` drives the whole run at a size a test can hold.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

#: at this size the system reads a widest gap of 0-0.005 over ten seeds,
#: the float8 control 0.015-0.083 on the same ones
TINY_GAP_LIMIT = 0.01
SERVED, PLACED = "tiny-chat", "tiny-churn"


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def make_root(base: Path) -> tuple:
    """(tiny root, benchmark dict with one served and one placement cell)."""
    (base / "bench" / "traffic").mkdir(parents=True)
    for d in ("drivers", "metrics", "configs"):
        (base / "bench" / d).symlink_to(ROOT / "bench" / d)
    lm = _json(ROOT / "bench" / "configs" / "smollm-135m.json")
    lm.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, intermediate_size=128,
              vocab_size=256, serving={"max_slots": 4, "max_len": 128, "dtype": "bfloat16"},
              correct_limits={"logit_gap": TINY_GAP_LIMIT})
    (base / "tiny-lm.json").write_text(json.dumps(lm))
    fleet = _json(ROOT / "bench" / "configs" / "fleet-a100-1024.json")
    # 160 GPUs: at or above 128 the engine deploys through the fabric
    fleet.update(n_gpus=160, nodes=20, compact_first_s=0.5, compact_period_s=1.0)
    (base / "tiny-fleet.json").write_text(json.dumps(fleet))
    chat = _json(ROOT / "bench" / "traffic" / "chat.json")
    chat.update(rate_per_s=10.0,
                prompt_len={"median": 24, "sigma": 0.8, "min": 8, "max": 64},
                output_len={"median": 8, "sigma": 0.7, "min": 4, "max": 32})
    (base / "bench" / "traffic" / "tiny-chat-mix.json").write_text(json.dumps(chat))
    churn = _json(ROOT / "bench" / "traffic" / "churn.json")
    churn.update(arrival_rate_per_s=50.0)
    (base / "bench" / "traffic" / "tiny-churn-mix.json").write_text(json.dumps(churn))
    bench = _json(ROOT / "BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-lm", "file": str(base / "tiny-lm.json")},
                        {"name": "tiny-fleet", "file": str(base / "tiny-fleet.json")}]
    bench["workloads"] = [
        {"name": SERVED, "config": "tiny-lm", "traffic": "tiny-chat-mix", "chips": 1},
        {"name": PLACED, "config": "tiny-fleet", "traffic": "tiny-churn-mix", "chips": 1},
    ]
    # metrics that name the real cells read the tiny ones instead
    real = {"smollm-chat": SERVED, "cp-churn-1024": PLACED}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [real.get(w, w) for w in m["workloads"]]
    return base, bench


def run(root, bench, cell, seed=7, seconds=2.0, trace=False, hook=None) -> dict:
    return harness.run(bench, cell, seed, seconds, trace, root=root,
                       require_tpu=False, hook=hook)

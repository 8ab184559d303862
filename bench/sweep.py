"""Sweep a cell's offered rate once, in one process, to find its knee: the
highest rate it sustains.  Not part of a run; the cell's traffic file then
fixes a rate at about four fifths of the knee.

    python3 bench/sweep.py --workload <cell> --rates 10,20,30 --seconds 20

Prints one JSON line per rate with the end-to-end metrics of that window
and what shows a backlog: requests still unfinished, or events processed
late, at the window's close.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402


def _reset(sut) -> None:
    """Drop what a served window left queued or in flight."""
    for e in getattr(sut, "engines", []):
        e.queue.clear()
        e.slots = [None] * len(e.slots)
        e.completed.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    harness.enable_cache()
    cell, cfg, mix = harness.find_cell(bench, args.workload, harness.ROOT)
    devices = harness.devices_for(cell["chips"], True)
    driver = harness.load_module(HERE / "drivers" / f"{cfg['driver']}.py")
    t0 = time.perf_counter()
    sut = driver.Cell(cfg, mix, args.seed, devices, harness.ROOT)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    key = next(k for k in mix if k.endswith("rate_per_s"))
    for rate in (float(r) for r in args.rates.split(",")):
        mix[key] = rate
        if cfg["driver"] == "placement":  # churn ids restart: a fresh fleet
            sut = driver.Cell(cfg, mix, args.seed, devices, harness.ROOT)
        rec = sut.window(args.seconds, jax.profiler.TraceAnnotation, harness.Tracer(False))
        rec.update(setup_s=0.0, trace=None, peaks=None)
        out = {"rate": rate, "attempted": rec["attempted"], "failed": rec["failed"]}
        for m in bench["end_to_end"]:
            if m["name"] != "setup_s":
                v = harness.load_module(HERE / "metrics" / f"{m['name']}.py").read(rec)
                if v is not None:
                    out[m["name"]] = v
        if rec["kind"] == "served":
            reqs = rec["requests"]
            ttft = [r["deliveries"][0][0] - r["due"] for r in reqs if r["deliveries"]]
            q = len(ttft) // 4
            out["ttft_first_quarter_ms"] = 1e3 * float(np.median(ttft[:q]))
            out["ttft_last_quarter_ms"] = 1e3 * float(np.median(ttft[-q:]))
            out["drain_s"] = max(r["deliveries"][-1][0] for r in reqs if r["deliveries"]) \
                - args.seconds
            toks = sum(n for r in reqs for _, n in r["deliveries"])
            out["tokens_per_s"] = toks / (args.seconds + max(0.0, out["drain_s"]))
        else:
            out["late_last_s"] = rec["late_s"][-1]
            out["compact_s"] = rec["compact_s"]
        print(json.dumps(out), flush=True)
        _reset(sut)
    return 0


if __name__ == "__main__":
    sys.exit(main())

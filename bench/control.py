"""Readings that set and test the limits of ``correct``; not part of a run.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, the check as a run makes it (the system's reading), then
the control on the same compared requests or events:

* served cells: the reference in float8 e4m3 (weights per output channel,
  inputs per row) in place of the bf16 system; its reading is the widest
  gap, under the float32 reference, of the token float8 puts first;
* placement cells: the reference with Table-1 index preference replaced by
  numeric order, replayed on the same events; its reading is the number of
  answers that differ from the reference's.

Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import reference_lm as lm  # noqa: E402


def served_control(sut) -> dict:
    return {"control_logit_gap": lm.widest_gap(
        lm.make_gap_fn(sut.shape, sut.ref.rope(sut.cfg), quant=True),
        sut.weights, sut.compared, sut.max_len, sut.mix["output_len"]["max"])}


def placement_control(sut) -> dict:
    place = harness.load_module(HERE / "drivers" / "placement.py")
    want = place.replay(sut.ref.initial_fleet(sut.cfg), sut.events)
    got = place.replay(sut.ref.initial_fleet(sut.cfg, numeric_indexes=True), sut.events)
    return {"control_answers_wrong": place.mismatches(want, *got)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell, cfg, mix = harness.find_cell(bench, args.workload, harness.ROOT)
        devices = harness.devices_for(cell["chips"], os.environ.get("JAX_PLATFORMS") != "cpu")
        driver = harness.load_module(HERE / "drivers" / f"{cfg['driver']}.py")
        sut = driver.Cell(cfg, mix, seed, devices, harness.ROOT)
        setup_s = time.perf_counter() - t0
        rec = sut.window(args.seconds, jax.profiler.TraceAnnotation, harness.Tracer(False))
        checks = {k: v for k, (v, _) in sut.check(rec).items()}
        if cfg["driver"] == "served":
            ctl = served_control(sut)
        else:
            ctl = placement_control(sut)
        print(json.dumps({"workload": args.workload, "seed": seed, "setup_s": setup_s,
                          "attempted": rec["attempted"], "failed": rec["failed"],
                          **checks, **ctl,
                          "seconds_total": time.perf_counter() - t0}), flush=True)
        sut.weights = None  # free the device before the next seed's set-up
        del sut
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Kernel micro-bench: per-call wall time of the jnp execution path on CPU
plus analytic FLOPs (the TPU-relevant number is the FLOPs/bytes profile; the
CPU microseconds only sanity-check that the memory-efficient paths run).

Shapes come from the calibration profiler's presets
(:data:`repro.obs.profile.PRESETS`) at whole-device size, so this bench and
``benchmarks/calibrate.py`` measure the same workloads.  Besides the
human-readable CSV on stdout, every run emits a machine-readable
``BENCH_kernels.json`` (same strict-JSON writer as ``placement_bench``)
with p50/p95 per kernel — the rows the ``validate_bench.py --baseline``
regression gate compares across commits.  A host-contention snapshot is
recorded (``host.contended``): timings taken next to a stale ``pytest`` or
a concurrent bench are flagged rather than silently trusted.

Usage: python -m benchmarks.kernel_bench [--preset full] [--json PATH]
"""
from __future__ import annotations

import argparse
import logging
import sys
import time
from typing import Dict, List

from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import profile

log = logging.getLogger("repro.bench.kernels")

#: schema tag of BENCH_kernels.json (validate_bench checks it).
KERNEL_BENCH_SCHEMA = "kernel_bench/v1"


def _timeit(fn, *args, n: int = 5, warmup: int = 1) -> List[float]:
    """Per-call wall times in seconds: ``warmup`` discarded calls (compile +
    caches), then ``n`` individually-timed synchronized calls.

    ``jax.block_until_ready`` handles tuple-returning ops (it synchronizes
    arbitrary pytrees), so each iteration invokes ``fn`` exactly once.
    """
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    walls = []
    for _ in range(max(n, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return walls


def run(preset: str = "full", reps: int = None, warmup: int = None
        ) -> Dict[str, Dict[str, float]]:
    """Run the preset's whole-device workloads; returns the ``kernels``
    section rows keyed ``kernel@shape``."""
    cfg = profile.PRESETS[preset]
    reps = int(cfg["reps"] if reps is None else reps)
    warmup = int(cfg["warmup"] if warmup is None else warmup)
    rows: Dict[str, Dict[str, float]] = {}
    print("kernel,shape,us_per_call,gflops_analytic")
    for wl in profile.whole_device_specs(preset):
        fn, args = wl.make()
        walls = sorted(_timeit(fn, *args, n=reps, warmup=warmup))
        timing = profile.KernelTiming(tuple(walls))
        p50 = timing.p50
        rows[f"{wl.kernel}@{wl.shape}"] = {
            "p50_us": p50 * 1e6,
            "p95_us": timing.p95 * 1e6,
            "min_us": walls[0] * 1e6,
            "mean_us": sum(walls) / len(walls) * 1e6,
            "reps": reps,
            "gflops_analytic": wl.flops / 1e9,
            "achieved_gflops_per_s": wl.flops / p50 / 1e9,
            "achieved_gbytes_per_s": wl.bytes / p50 / 1e9,
            "tokens_per_s": wl.tokens / p50,
        }
        print(f"{wl.kernel},{wl.shape},{p50 * 1e6:.0f},{wl.flops / 1e9:.2f}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="full",
                    choices=sorted(profile.PRESETS))
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--verbose", "-v", action="store_true")
    args = ap.parse_args(argv)

    logging.basicConfig(
        stream=sys.stderr,
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(message)s",
    )

    host = obs.host_snapshot()
    report = {
        "args": {"preset": args.preset, "reps": args.reps,
                 "warmup": args.warmup},
        "host": host,
        "jax_device": obs.device_snapshot(),
        "kernels": run(args.preset, args.reps, args.warmup),
    }
    if obs.write_report(args.json, report, KERNEL_BENCH_SCHEMA):
        log.info("wrote %s%s", args.json,
                 " (CONTENDED host — timings suspect)"
                 if host["contended"] else "")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())

"""The benchmark's core: run one cell once and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything specific lives in files found by the names in ``BENCHMARK.json``:
a cell names a configuration (``configs[].file``, whose ``driver`` key names
``bench/drivers/<driver>.py`` and whose ``reference`` key names its plain
reference) and a traffic mix (``bench/traffic/<traffic>.json``); each metric
is read by ``bench/metrics/<metric>.py``, whose ``read(record)`` returns a
number or None.

A driver's ``Cell(cfg, mix, seed, devices, root)`` builds and warms the
system; ``window(seconds, span, tracer)`` drives it for the window, calling
``tracer.tick(now)`` between calls into the system, and returns the record
the readers see (times in seconds from the window's start);
``check(record)`` frees the system's state, runs the reference and returns
``{name: (value, limit)}``: the run is correct when every value is at most
its limit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
#: harness spans around the calls into the system; idle gaps are named by them
SPANS = ("submit", "pump", "wait", "deploy", "depart", "compact", "observe")
#: a traced run profiles this part of its window (seconds from its start),
#: unless the driver names its own ``TRACE_SPAN``
TRACE_FROM_S, TRACE_TO_S = 5.0, 15.0


class Tracer:
    """Starts the profiler at the driver's first ``tick`` at or past
    ``start`` seconds into the window and stops it at the first past
    ``stop``; drivers tick between calls into the system, never inside one.
    ``t0``/``t1`` are the window-relative times it ran between, ``seconds``
    its length on the host clock."""

    def __init__(self, on: bool, span=None):
        self.on = on
        self.start, self.stop = span or (TRACE_FROM_S, TRACE_TO_S)
        self.t0 = self.t1 = None
        self.seconds = 0.0

    def tick(self, now: float) -> None:
        if not self.on:
            return
        import jax

        if self.t0 is None and now >= self.start:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # device ops and host spans; no tracing of every Python call,
            # which slows a host-bound loop many times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.t0, self._p0 = now, time.perf_counter()
        elif self.t0 is not None and self.t1 is None and now >= self.stop:
            self._end()

    def _end(self) -> None:
        import jax

        self.seconds = time.perf_counter() - self._p0
        self.t1 = self.t0 + self.seconds
        jax.profiler.stop_trace()  # writes the trace: blocks, after t1

    def close(self) -> None:
        """Stop a trace the window ended inside."""
        if self.t0 is not None and self.t1 is None:
            self._end()


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '-' or '.')."""
    key = f"bench_file:{Path(path).resolve()}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str, root: Path):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, mix


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _compile_counter():
    """Counts XLA compilations from here on (jax.monitoring events)."""
    import jax

    box = {"n": 0}

    def on_event(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return box


def _metrics(specs, cell_name: str, rec: dict, root: Path, required: bool) -> dict:
    out = {}
    for m in specs:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        val = load_module(root / "bench" / "metrics" / f"{m['name']}.py").read(rec)
        if val is None:
            if required:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        if not math.isfinite(val):
            raise RuntimeError(f"metric {m['name']} is {val}")
        out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def run(bench: dict, name: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, t_start: Optional[float] = None,
        require_tpu: bool = True, hook: Optional[Callable] = None) -> dict:
    """One run of one cell; returns the result object (``checks`` last).
    ``hook(sut)``, for tests, may break the system after set-up."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell, cfg, mix = find_cell(bench, name, root)
    devices = devices_for(cell["chips"], require_tpu)
    import jax

    peaks = None
    if require_tpu:
        import peaks as peak_table

        peaks = peak_table.peaks_for(devices[0].device_kind)
    driver = load_module(root / "bench" / "drivers" / f"{cfg['driver']}.py")
    compiles = _compile_counter()

    sut = driver.Cell(cfg, mix, seed, devices, root)
    if hook is not None:
        hook(sut)
    setup_s = time.perf_counter() - t_start

    span = jax.profiler.TraceAnnotation
    n0 = compiles["n"]
    tracer = Tracer(trace, getattr(driver, "TRACE_SPAN", None))
    rec = sut.window(seconds, span, tracer)
    tracer.close()
    reduced = None
    if tracer.t1 is not None:
        import trace_reduce

        reduced = trace_reduce.reduce_trace(trace_reduce.latest_xplane(str(TRACE_DIR)),
                                            SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiled_in_window = compiles["n"] - n0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    checks = sut.check(rec)
    rec.update(setup_s=setup_s, trace=reduced, peaks=peaks,
               trace_interval=(tracer.t0, tracer.t1), traced_s=tracer.seconds)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        metrics = _metrics(bench["per_layer"], name, rec, root, required=False)
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"], window_s=tracer.seconds)
    else:
        metrics = _metrics(bench["end_to_end"], name, rec, root, required=True)
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    late = sorted(rec.get("late_s", [])) or [0.0]
    result["notes"] = {
        "compiled_in_window": compiled_in_window,
        "generator_late_p50_s": late[len(late) // 2],
        "generator_late_max_s": late[-1],
    }
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def enable_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed place (or
    where JAX_COMPILATION_CACHE_DIR says), every program in it however fast
    it compiles, so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None, t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    enable_cache()
    res = run(bench, args.workload, args.seed, args.seconds, bool(args.trace),
              t_start=t_start)
    notes = res.pop("notes")
    print("notes " + json.dumps(notes), flush=True)
    for k, c in res["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0

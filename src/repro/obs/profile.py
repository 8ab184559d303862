"""Kernel calibration profiler: measure what each MIG slice can serve.

Closes the measure -> model -> plan loop (ROADMAP item 3).  The placement
stack plans against :class:`repro.core.perfmodel.PerfModel`, which until
this subsystem shipped was a hand-written whole-device rate table.  This
module runs the actual ``repro.kernels`` ops — flash attention (prefill),
decode attention (decode), and the SSD scan — across **MIG-profile-shaped
problem sizes** and derives measured prefill/decode service rates per
partition profile, producing:

* per-rep wall-time observations in the active :mod:`repro.obs` metrics
  registry (``kernel_wall_seconds{kernel,device,profile}`` histograms);
* a schema-validated ``CALIBRATION.json`` artifact
  (:data:`CALIBRATION_SCHEMA`) that ``PerfModel.from_calibration`` loads
  back into the planning stack, and that the CI regression gate
  (:mod:`benchmarks.validate_bench`) checks structurally.

Slice emulation
---------------
A profile with ``c`` of the device's compute slices and ``m`` of its
memory slices gets a problem scaled to its budget: the prefill batch
scales with the compute fraction (prefill is compute-bound), the decode
batch with the memory fraction (decode bandwidth travels with the memory
slices — the MISO observation).  On a host **without** real MIG
partitions (CPU CI, a whole GPU) the kernel still sees the full machine,
so measured per-token cost captures only the *shape* efficiency; the
slice's compute/memory fraction is then applied analytically
(``emulate=True``, recorded as ``emulated`` in the artifact).  On real
MIG hardware, run this same profiler inside each GPU instance with
``emulate=False`` and the fraction drops out of the measurement itself.

The sweep additionally fits an effective ``parallel_efficiency`` exponent
from the sub-whole-device measurements (``rate_p / rate_whole =
frac**e``): shape-dependent per-token overheads at small slices surface
as ``e < 1``, exactly the sublinear knob ``PerfModel`` already exposes.

Timing discipline: every measurement jits the op once, runs ``warmup``
discarded iterations (compile + cache effects), then times ``reps``
individual iterations with ``block_until_ready`` around each — the same
regimen as ``benchmarks/kernel_bench.py``, which shares these specs.
Inputs come from fixed seeds, so the measured *structure* (shapes, FLOPs,
bytes, tokens) is deterministic; only wall times vary by host.
"""
from __future__ import annotations

import dataclasses
import logging
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import get_telemetry
from .host import device_snapshot, host_snapshot

log = logging.getLogger("repro.obs.profile")

__all__ = [
    "CALIBRATION_SCHEMA",
    "PRESETS",
    "KernelTiming",
    "measure",
    "whole_device_specs",
    "run_calibration",
]

#: schema tag of the CALIBRATION.json artifact (validate_bench checks it).
CALIBRATION_SCHEMA = "calibration/v1"

#: problem-size presets: whole-device base shapes per kernel plus the
#: default timing discipline.  ``tiny`` is the CI smoke (seconds on one
#: CPU); ``full`` matches the historical kernel_bench shapes.
PRESETS: Dict[str, Dict[str, object]] = {
    "tiny": dict(
        flash=dict(b=2, s=256, hq=4, hkv=2, d=64),
        decode=dict(b=4, smax=256, hq=4, hkv=2, d=64),
        ssd=dict(b=2, s=256, h=2, p=16, n=8),
        reps=3, warmup=1,
    ),
    "small": dict(
        flash=dict(b=4, s=1024, hq=8, hkv=2, d=64),
        decode=dict(b=16, smax=2048, hq=8, hkv=2, d=64),
        ssd=dict(b=2, s=512, h=4, p=32, n=16),
        reps=5, warmup=2,
    ),
    "full": dict(
        flash=dict(b=8, s=2048, hq=8, hkv=2, d=64),
        decode=dict(b=32, smax=8192, hq=8, hkv=2, d=64),
        ssd=dict(b=4, s=1024, h=4, p=32, n=16),
        reps=10, warmup=3,
    ),
}

#: fitted parallel-efficiency samples are clamped here before averaging —
#: tiny-shape noise must not push the exponent out of PerfModel's (0, 1].
_EFF_CLAMP = (0.25, 1.0)


def _pct(sorted_vals: Sequence[float], q: float) -> float:
    """numpy-style linear-interpolation percentile of pre-sorted values."""
    if not sorted_vals:
        return float("nan")
    pos = (len(sorted_vals) - 1) * (q / 100.0)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi:
        return sorted_vals[lo]
    frac = pos - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


@dataclasses.dataclass(frozen=True)
class KernelTiming:
    """Warm-up-disciplined wall times of one (kernel, shape) measurement."""

    wall_s: Tuple[float, ...]  # per-rep seconds, chronological

    @property
    def p50(self) -> float:
        return _pct(sorted(self.wall_s), 50.0)

    @property
    def p95(self) -> float:
        return _pct(sorted(self.wall_s), 95.0)

    def as_dict(self) -> Dict[str, float]:
        s = sorted(self.wall_s)
        return {
            "reps": len(s),
            "min": s[0],
            "mean": sum(s) / len(s),
            "p50": _pct(s, 50.0),
            "p95": _pct(s, 95.0),
        }


def measure(
    fn: Callable,
    *args,
    reps: int = 5,
    warmup: int = 2,
    labels: Optional[Dict[str, str]] = None,
) -> KernelTiming:
    """Time ``fn(*args)``: ``warmup`` discarded calls, then ``reps`` timed
    calls, each synchronized with ``jax.block_until_ready``.

    Each rep is observed into the active telemetry's
    ``kernel_wall_seconds`` histogram under ``labels`` (no-op when
    telemetry is disabled — same discipline as the rest of the stack).
    """
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args))
    tel = get_telemetry()
    hist = tel.metrics.histogram(
        "kernel_wall_seconds", "per-rep kernel wall time", labels=labels or {}
    )
    walls: List[float] = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        walls.append(dt)
        hist.observe(dt)
    return KernelTiming(tuple(walls))


# ---------------------------------------------------------------------------
# kernel workload specs (shared with benchmarks/kernel_bench.py)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _Workload:
    """One concrete (kernel, shape): inputs, analytics, token accounting."""

    kernel: str
    shape: str
    make: Callable[[], Tuple]  # () -> (jitted fn, args)
    tokens: int  # tokens processed per call (prefill: B*S; decode: B)
    flops: float
    bytes: float


def _flash_workload(b: int, s: int, hq: int, hkv: int, d: int) -> _Workload:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    def make():
        key = jax.random.key(0)
        q = jax.random.normal(key, (b, s, hq, d), jnp.float32)
        k = jax.random.normal(key, (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(key, (b, s, hkv, d), jnp.float32)
        fn = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
        return fn, (q, k, v)

    flops = 4 * b * s * s * hq * d / 2  # causal halves the score matmul
    byts = 4.0 * (2 * b * s * hq * d + 2 * b * s * hkv * d)
    return _Workload("flash_attention", f"B{b}xS{s}xH{hq}/{hkv}xD{d}",
                     make, b * s, flops, byts)


def _decode_workload(b: int, smax: int, hq: int, hkv: int, d: int) -> _Workload:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    def make():
        key = jax.random.key(0)
        q = jax.random.normal(key, (b, 1, hq, d), jnp.float32)
        k = jax.random.normal(key, (b, hkv, d, smax), jnp.float32)
        v = jax.random.normal(key, (b, hkv, d, smax), jnp.float32)
        lens = jnp.full((b,), smax // 2, jnp.int32)
        fn = jax.jit(lambda q, k, v, l: ops.decode_attention(q, k, v, l))
        return fn, (q, k, v, lens)

    flops = 4.0 * b * smax * hq * d
    byts = 4.0 * (2 * b * hq * d + 2 * b * smax * hkv * d) + 4.0 * b
    return _Workload("decode_attention", f"B{b}xS{smax}ragged",
                     make, b, flops, byts)


def _ssd_workload(b: int, s: int, h: int, p: int, n: int) -> _Workload:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    def make():
        key = jax.random.key(0)
        x = jax.random.normal(key, (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(key, (b, s, h), jnp.float32))
        A = -jnp.ones((h,), jnp.float32)
        B_ = jax.random.normal(key, (b, s, n), jnp.float32)
        C = jax.random.normal(key, (b, s, n), jnp.float32)
        chunk = min(256, s)
        fn = jax.jit(lambda *a: ops.ssd_scan(*a, chunk=chunk))
        return fn, (x, dt, A, B_, C)

    flops = 2.0 * b * s * h * p * n * 2
    byts = 4.0 * (2 * b * s * h * p + b * s * h + 2 * b * s * n + b * h * p * n)
    return _Workload("ssd_scan", f"B{b}xS{s}xH{h}xP{p}xN{n}",
                     make, b * s, flops, byts)


def whole_device_specs(preset: str = "full") -> List[_Workload]:
    """The preset's whole-device workloads (kernel_bench runs exactly these)."""
    cfg = PRESETS[preset]
    return [
        _flash_workload(**cfg["flash"]),
        _decode_workload(**cfg["decode"]),
        _ssd_workload(**cfg["ssd"]),
    ]


def _scaled(base: int, frac: float) -> int:
    return max(1, round(base * frac))


# ---------------------------------------------------------------------------
# the profile sweep
# ---------------------------------------------------------------------------
def _sweep_profiles(device) -> List:
    """Profiles to measure: distinct (compute, memory) footprints, big->small
    (the ``+me`` variant duplicates its base profile's budget — skip it)."""
    seen = set()
    out = []
    for prof in device.profiles_sorted_desc():
        key = (prof.compute_slices, prof.memory_slices)
        if key in seen:
            continue
        seen.add(key)
        out.append(prof)
    return out


def _timing_row(wl: _Workload, device_name: str, prof, cfrac: float,
                mfrac: float, reps: int, warmup: int) -> Dict[str, object]:
    fn, args = wl.make()
    timing = measure(
        fn, *args, reps=reps, warmup=warmup,
        labels={"kernel": wl.kernel, "device": device_name, "profile": prof.name},
    )
    p50 = timing.p50
    return {
        "kernel": wl.kernel,
        "device": device_name,
        "profile_id": prof.profile_id,
        "profile": prof.name,
        "compute_frac": cfrac,
        "memory_frac": mfrac,
        "shape": wl.shape,
        "tokens": wl.tokens,
        "flops": wl.flops,
        "bytes": wl.bytes,
        "wall_s": timing.as_dict(),
        "tokens_per_s": wl.tokens / p50 if p50 > 0 else float("nan"),
        "achieved_gflops_per_s": wl.flops / p50 / 1e9 if p50 > 0 else float("nan"),
        "achieved_gbytes_per_s": wl.bytes / p50 / 1e9 if p50 > 0 else float("nan"),
    }


def _fit_efficiency(samples: List[Tuple[float, float]]) -> float:
    """Effective parallel-efficiency exponent from (frac, eff_ratio) pairs,
    where ``eff_ratio`` is the slice-shaped run's per-token rate over the
    whole-device per-token rate: ``rate_p/rate_whole = frac**e`` with the
    fraction applied analytically gives ``e = 1 + ln(eff)/ln(frac)``."""
    es = []
    for frac, eff in samples:
        if not (0.0 < frac < 1.0) or not (eff > 0.0) or not math.isfinite(eff):
            continue
        e = 1.0 + math.log(eff) / math.log(frac)
        es.append(min(max(e, _EFF_CLAMP[0]), _EFF_CLAMP[1]))
    if not es:
        return 1.0
    return sum(es) / len(es)


def profile_device(
    device,
    preset: str = "small",
    reps: Optional[int] = None,
    warmup: Optional[int] = None,
    emulate: bool = True,
) -> Tuple[Dict[str, object], List[Dict[str, object]]]:
    """Measure one device model across its profile ladder.

    Returns ``(device_entry, kernel_rows)``: the former is the
    ``devices[<name>]`` section of the calibration artifact (whole-device
    rates, per-profile rates, fitted ``parallel_efficiency``), the latter
    the raw per-(kernel, profile) measurement rows.
    """
    cfg = PRESETS[preset]
    reps = int(cfg["reps"] if reps is None else reps)
    warmup = int(cfg["warmup"] if warmup is None else warmup)
    flash, decode, ssd = cfg["flash"], cfg["decode"], cfg["ssd"]

    rows: List[Dict[str, object]] = []
    profiles_entry: Dict[str, Dict[str, object]] = {}
    whole: Dict[str, float] = {}
    eff_samples: List[Tuple[float, float]] = []
    whole_rate: Dict[str, float] = {}  # kernel -> whole-device tokens/s (raw)

    for prof in _sweep_profiles(device):
        cfrac = prof.compute_slices / device.n_gpu_slices
        mfrac = prof.memory_slices / device.n_memory_slices
        workloads = (
            _flash_workload(**{**flash, "b": _scaled(flash["b"], cfrac)}),
            _decode_workload(**{**decode, "b": _scaled(decode["b"], mfrac)}),
            _ssd_workload(**{**ssd, "b": _scaled(ssd["b"], cfrac)}),
        )
        log.info("profiling %s / %s (c=%d/%d m=%d/%d) ...",
                 device.name, prof.name, prof.compute_slices,
                 device.n_gpu_slices, prof.memory_slices,
                 device.n_memory_slices)
        by_kernel: Dict[str, Dict[str, object]] = {}
        for wl in workloads:
            row = _timing_row(wl, device.name, prof, cfrac, mfrac, reps, warmup)
            rows.append(row)
            by_kernel[wl.kernel] = row

        raw_prefill = float(by_kernel["flash_attention"]["tokens_per_s"])
        raw_decode = float(by_kernel["decode_attention"]["tokens_per_s"])
        # on non-MIG hosts the kernel saw the whole machine: apply the
        # slice's fraction analytically (see module docstring).
        prefill_tps = raw_prefill * (cfrac if emulate else 1.0)
        decode_tps = raw_decode * (mfrac if emulate else 1.0)
        is_whole = (prof.compute_slices == device.n_gpu_slices)
        if is_whole:
            whole = {
                "prefill_tokens_per_s": prefill_tps,
                "decode_tokens_per_s": decode_tps,
            }
            whole_rate = {"prefill": raw_prefill, "decode": raw_decode}
        else:
            if whole_rate.get("prefill"):
                eff_samples.append((cfrac, raw_prefill / whole_rate["prefill"]))
            if whole_rate.get("decode"):
                eff_samples.append((mfrac, raw_decode / whole_rate["decode"]))
        profiles_entry[str(prof.profile_id)] = {
            "name": prof.name,
            "compute_frac": cfrac,
            "memory_frac": mfrac,
            "prefill_tokens_per_s": prefill_tps,
            "decode_tokens_per_s": decode_tps,
        }

    entry = {
        "whole_device": whole,
        "parallel_efficiency": _fit_efficiency(eff_samples),
        "emulated": emulate,
        "profiles": profiles_entry,
    }
    return entry, rows


def run_calibration(
    devices: Optional[Sequence] = None,
    preset: str = "small",
    reps: Optional[int] = None,
    warmup: Optional[int] = None,
    emulate: bool = True,
    impl: Optional[str] = None,
) -> Dict[str, object]:
    """The full calibration sweep -> a ``CALIBRATION.json``-shaped dict.

    Write it with ``obs.write_report(path, report, CALIBRATION_SCHEMA)``
    (the :mod:`benchmarks.calibrate` driver does exactly that) and load it
    back with ``PerfModel.from_calibration(path)``.
    """
    from repro.core.profiles import A100_80GB
    from repro.kernels import ops

    if impl is not None:
        ops.set_impl(impl)
    devices = list(devices) if devices else [A100_80GB]
    host = host_snapshot()

    report: Dict[str, object] = {
        "config": {
            "preset": preset,
            "reps": reps if reps is not None else PRESETS[preset]["reps"],
            "warmup": warmup if warmup is not None else PRESETS[preset]["warmup"],
            "emulated": emulate,
            "impl": ops.get_impl(),
            "devices": [d.name for d in devices],
        },
        "host": host,
        "jax_device": device_snapshot(),
        "devices": {},
        "kernels": [],
    }
    for device in devices:
        entry, rows = profile_device(
            device, preset=preset, reps=reps, warmup=warmup, emulate=emulate
        )
        report["devices"][device.name] = entry
        report["kernels"].extend(rows)
    return report

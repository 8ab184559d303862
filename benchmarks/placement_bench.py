"""Paper Sec-5 evaluation: Figures 9 (initial deployment), 10 (compaction),
11 (reconfiguration), on 8-GPU and 80-GPU clusters, 100 random test cases.

Approaches (paper Sec 5.1) — all routed through core.engine.PlacementEngine:
  first_fit      — GPUs/workloads by id, indexes from 0
  load_balanced  — GPUs by joint slice utilization ascending, indexes from 0
  rule_based     — Sec-4.2 heuristic (ours)
  mip            — WPM with existing placements fixed (ours)
  joint_mip      — WPM jointly re-placing existing workloads (ours; Fig 9 only)
  patterns       — beyond-paper pattern-enumeration exact solver (reconfig only)

Every approach is scored with the Table-3 metrics averaged over test cases,
then normalized against the highest value per metric (as the paper plots).

Usage:
  python -m benchmarks.placement_bench --case initial --gpus 8 --cases 100
  python -m benchmarks.placement_bench --trace --gpus 8 --tpu-pods 2 \\
      --horizon 200 --policies first_fit load_balanced rule_based
  python -m benchmarks.placement_bench --fleet-scale 256 1024

``--trace`` switches to the online mode: a seeded arrival/departure/burst
trace over a mixed A100 + TPU-pod fleet, periodic compaction with an
optional migration budget, reporting time-averaged GPUs-used and wastage.

``--autoscale`` switches to the demand-driven mode: seeded request traffic
(phase-shifted diurnal chat models + a flash-crowd embedding model) drives
the traffic/perf/autoscaler subsystem over an A100 fleet; rows are
controller x rate-scale x commit-mode, columns SLO attainment / GPUs-used /
disruption-minutes.  ``static`` rows are the peak-provisioned baseline the
closed loop must beat.

``--calibrated CALIBRATION.json`` (with ``--autoscale``) re-runs the grid
on a measured ``PerfModel`` loaded from the kernel calibration artifact
(``benchmarks/calibrate.py``): rows gain ``@cal`` variants and the report
a ``calibration_delta`` section — how far the hand-written rate table was
from measured kernel rates, in attainment and GPUs-used.

``--faults`` switches to the chaos mode: the demand scenario (with the
embedding model demoted to the best-effort brownout tier) is replayed
clean and again under a seeded ``FaultInjector`` schedule (GPU failures
spread mid-trace + node drains at 70% horizon) per commit mode.  The
report (``BENCH_failures.json``, schema ``failures_bench/v1``) carries
per-run fault/recovery columns, a ``retention`` section (faulted/clean
SLO attainment, recovery-time-to-full-capacity, capacity-lost
GPU-seconds), the injected schedule, and the ``fault_byte_identity``
flag — a wired-but-empty injector must reproduce the clean trace.

``--fleet-scale`` benchmarks the vectorized placement fabric
(core/fabric.py) against the scalar path on large fleets: per size, one
deploy of a ~60%-load test case through first_fit and rule_based with the
fabric off vs on (placements are identical — the speedup is free), plus the
fabric-native frag_aware policy, plus a short online trace per policy.

Every run also emits a machine-readable ``BENCH_placement.json`` (disable
with ``--json ''``) so the repo's perf trajectory is tracked across PRs.
The JSON is strict (non-finite floats serialize as ``null``, never ``NaN``).

``--telemetry`` opts the run into the ``repro.obs`` subsystem: engine verbs
are span-traced, planner-latency p50/p95/p99 per verb land in the JSON
report (``planner_latency`` section), and the run writes a JSONL span/event
dump plus a Prometheus text exposition next to the report (render the JSONL
with ``python -m repro.obs.report``).

Human-readable tables go through the std ``logging`` module on stderr
(``--verbose`` adds debug/timing chatter), so stdout stays clean for
machine consumers.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import math
import sys
import time
from typing import Dict, Optional, Sequence, Tuple

from repro import obs
from repro.core import metrics
from repro.core.autoscaler import SLO, Autoscaler, AutoscalerConfig
from repro.core.engine import PlacementEngine
from repro.core.events import (
    DemandSimulator,
    ModelServiceSpec,
    OnlineSimulator,
    build_fleet,
    generate_trace,
)
from repro.core.faults import FaultInjector, FaultSpec
from repro.core.perfmodel import PerfModel
from repro.core.profiles import A100_80GB
from repro.core.simulator import TestCase, generate_test_case
from repro.core.tpu_profiles import TPU_V5E_POD
from repro.core.traffic import DiurnalRate, FlashCrowd, ModelTraffic, generate_requests
from repro.launch.compile_cache import enable_compile_cache

#: human-readable output channel (tables, timings) — stderr via logging, so
#: stdout never interleaves human text with telemetry/JSON consumers.
log = logging.getLogger("repro.bench")

APPROACHES = {
    "initial": ("first_fit", "load_balanced", "rule_based", "frag_aware",
                "mip", "joint_mip"),
    "compaction": ("first_fit", "load_balanced", "rule_based", "frag_aware",
                   "mip"),
    "reconfiguration": ("first_fit", "load_balanced", "rule_based",
                        "frag_aware", "mip", "patterns"),
}

_METRICS = (
    "n_gpus", "memory_wastage", "compute_wastage", "availability",
    "migration_size", "pending_model_size", "sequential_migrations",
    "memory_utilization", "compute_utilization", "fragmentation",
)


def _run(case: str, tc: TestCase, approach: str, time_limit: float):
    """One test case through the unified engine; returns (state, pending, secs)."""
    st = tc.initial.clone()
    eng = PlacementEngine(approach, time_limit=time_limit)
    if case == "initial":
        res = eng.deploy(st, tc.new_workloads)
    elif case == "compaction":
        res = eng.compact(st)
    elif case == "reconfiguration":
        res = eng.reconfigure(st)
    else:
        raise ValueError(case)
    return st, res.pending, res.seconds


def run_case(
    case: str,
    n_gpus: int,
    n_cases: int,
    time_limit: float,
    mip_cases: Optional[int] = None,
    approaches: Optional[Sequence[str]] = None,
) -> Dict[str, Dict[str, float]]:
    """Returns {approach: {metric: mean}} plus solve-time and seq-migration."""
    approaches = approaches or APPROACHES[case]
    sums: Dict[str, Dict[str, float]] = {a: {m: 0.0 for m in _METRICS} for a in approaches}
    counts: Dict[str, int] = {a: 0 for a in approaches}
    for a in approaches:
        sums[a]["solve_seconds"] = 0.0
        n = n_cases
        if mip_cases is not None and a in ("mip", "joint_mip", "patterns"):
            n = min(n, mip_cases)
        for seed in range(n):
            tc = generate_test_case(seed, n_gpus=n_gpus)
            # compaction/reconfiguration act on existing workloads only —
            # pending is null for them by construction (paper Sec 5.2.2)
            all_wl = list(tc.initial.workloads.values())
            if case == "initial":
                all_wl += list(tc.new_workloads)
            final, pending, secs = _run(case, tc, a, time_limit)
            final.validate()
            m = metrics.evaluate(final, tc.initial, all_wl)
            for k in _METRICS:
                sums[a][k] += float(getattr(m, k))
            sums[a]["solve_seconds"] += secs
            counts[a] += 1
    return {
        a: {k: v / max(counts[a], 1) for k, v in sums[a].items()} for a in approaches
    }


def normalize(table: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """Paper-style: each metric normalized against its max across approaches."""
    out: Dict[str, Dict[str, float]] = {a: {} for a in table}
    keys = next(iter(table.values())).keys()
    for k in keys:
        mx = max(abs(table[a][k]) for a in table) or 1.0
        for a in table:
            out[a][k] = table[a][k] / mx
    return out


def print_table(case: str, n_gpus: int, table: Dict[str, Dict[str, float]]) -> None:
    norm = normalize(table)
    keys = list(next(iter(table.values())).keys())
    log.info(f"\n== {case} @ {n_gpus} GPUs (mean over cases; normalized in []) ==")
    header = "approach".ljust(15) + "".join(k[:14].rjust(16) for k in keys)
    log.info(header)
    for a, row in table.items():
        line = a.ljust(15)
        for k in keys:
            line += f"{row[k]:9.3f}[{norm[a][k]:4.2f}]".rjust(16)
        log.info(line)


# ---------------------------------------------------------------------------
# online trace mode (--trace)
# ---------------------------------------------------------------------------
#: TraceStats field -> short column label (migration-cost columns included)
_TRACE_COLS = {
    "time_avg_gpus_used": "avg_gpus",
    "time_avg_compute_waste": "avg_cwaste",
    "time_avg_mem_occupancy": "avg_mem_occ",
    "peak_gpus_used": "peak_gpus",
    "n_placed": "placed",
    "n_rejected": "rejected",
    "n_migrations": "migrations",
    "n_compactions": "compactions",
    "n_plans_rejected": "plans_rej",
    "n_deferred": "deferred",  # compactions + reconfigures inside a window
    "gib_moved": "gib_moved",
    "disruption_minutes": "disrupt_min",
    "migration_window_seconds": "migr_win_s",
    "engine_seconds": "engine_s",
}


def run_trace(
    policies: Sequence[str],
    n_a100: int,
    n_tpu_pods: int,
    seed: int,
    horizon: float,
    arrival_rate: float,
    mean_lifetime: float,
    compact_every: Optional[float],
    migration_budget: Optional[int],
    time_limit: float,
    commit_modes: Sequence[str] = ("always",),
    reconfigure_every: Optional[float] = None,
) -> Dict[str, Dict[str, float]]:
    """Each policy x commit mode over the same seeded trace.

    Rows are keyed ``policy`` when one commit mode is given, else
    ``policy@mode`` — the side-by-side view behind the control plane's
    headline: net-positive cuts disruption-minutes at equal GPUs-used.
    """
    spec = [(A100_80GB, n_a100)]
    if n_tpu_pods:
        spec.append((TPU_V5E_POD, n_tpu_pods))
    out: Dict[str, Dict[str, float]] = {}
    for policy in policies:
        for commit in commit_modes:
            fleet = build_fleet(spec)
            trace = generate_trace(
                seed, fleet, horizon=horizon, arrival_rate=arrival_rate,
                mean_lifetime=mean_lifetime,
            )
            sim = OnlineSimulator(
                fleet,
                PlacementEngine(policy, time_limit=time_limit, commit=commit),
                compact_every=compact_every,
                migration_budget=migration_budget,
                reconfigure_every=reconfigure_every,
            )
            stats = sim.run(trace)
            fleet.validate()
            d = stats.as_dict()
            d["gib_moved"] = stats.bytes_moved / 2**30
            d["n_deferred"] = (
                stats.n_compactions_deferred + stats.n_reconfigures_deferred
            )
            key = policy if len(commit_modes) == 1 else f"{policy}@{commit}"
            out[key] = {k: float(d[k]) for k in _TRACE_COLS}
    return out


def print_trace_table(table: Dict[str, Dict[str, float]], header: str) -> None:
    log.info(f"\n== online trace: {header} ==")
    cols = list(next(iter(table.values())).keys())
    width = max(24, max(len(a) for a in table) + 2)
    log.info("policy".ljust(width) + "".join(_TRACE_COLS[c].rjust(13) for c in cols))
    for a, row in table.items():
        log.info(a.ljust(width) + "".join(f"{row[c]:13.3f}" for c in cols))


# ---------------------------------------------------------------------------
# autoscale mode (--autoscale): demand-driven traffic + replica controller
# ---------------------------------------------------------------------------
#: default demand scenario: three phase-shifted diurnal chat models plus one
#: flash-crowd embedding model, on A100 MIG profiles.  ``rate_scale``
#: multiplies every base rate; the diurnal period is the trace horizon (one
#: simulated "day" per run).  (profile, ladder, traffic args) per model.
_SCENARIO = (
    ("chat-l", 5, (), dict(base_rps=100.0, amplitude=0.7, phase=0.0), 512, 128),
    ("chat-m", 9, (), dict(base_rps=75.0, amplitude=0.8, phase=0.5), 512, 96),
    ("bot-s", 15, (15, 19), dict(base_rps=40.0, amplitude=0.6, phase=0.25), 256, 32),
    ("embed", 19, (), None, 128, 4),  # FlashCrowd (mid-trace spike)
)

_AUTOSCALE_COLS = {
    "slo_attainment": "slo_attain",
    "ttft_p95": "ttft_p95",
    "time_avg_gpus_used": "avg_gpus",
    "peak_gpus_used": "peak_gpus",
    "time_avg_queue_depth": "avg_queue",
    "n_requests": "requests",
    "n_unserved": "unserved",
    "n_scale_ups": "ups",
    "n_scale_downs": "downs",
    "n_resizes": "resizes",
    "n_deploy_rejected": "deploy_rej",
    "n_plans_rejected": "plans_rej",
    "disruption_minutes": "disrupt_min",
    "gib_moved": "gib_moved",
    "engine_seconds": "engine_s",
}


def _scenario_specs(rate_scale: float, horizon: float, slo: SLO):
    """(ModelServiceSpec list, ModelTraffic list, peak rps per model)."""
    specs, traffic, peaks = [], [], {}
    for model, pid, ladder, diurnal, mean_p, mean_d in _SCENARIO:
        if diurnal is not None:
            pat = DiurnalRate(
                base_rps=diurnal["base_rps"] * rate_scale,
                amplitude=diurnal["amplitude"],
                period=horizon,
                phase=diurnal["phase"] * horizon,
            )
        else:
            pat = FlashCrowd(
                base_rps=20.0 * rate_scale,
                flash_at=horizon * 0.4,
                flash_duration=horizon * 0.15,
                multiplier=4.0,
            )
        specs.append(ModelServiceSpec(
            model=model, profile_id=pid, profile_ladder=ladder, slo=slo,
        ))
        traffic.append(ModelTraffic(
            model=model, pattern=pat,
            mean_prompt_len=mean_p, mean_decode_len=mean_d,
        ))
        peaks[model] = pat.peak_rate
    return specs, traffic, peaks


def _static_replicas(spec: ModelServiceSpec, traffic: ModelTraffic,
                     peak_rps: float, perf: PerfModel, rho: float) -> int:
    """Peak-provisioned static sizing (the no-autoscaler baseline)."""
    cap = perf.capacity_rps(
        A100_80GB, spec.profile_id,
        traffic.mean_prompt_len, traffic.mean_decode_len,
    )
    return max(1, math.ceil(peak_rps / (rho * cap)))


def run_autoscale(
    policy: str,
    n_gpus: int,
    seed: int,
    horizon: float,
    rate_scales: Sequence[float],
    controllers: Sequence[str],
    commit_modes: Sequence[str],
    compact_every: Optional[float],
    autoscale_every: float,
    perf: Optional[PerfModel] = None,
) -> Dict[str, Dict[str, float]]:
    """Rate-sweep x controller x commit grid over the demand scenario.

    ``static`` rows provision every model for its PEAK rate up front and
    never scale — the over-provisioning baseline the closed loop must beat
    on time-averaged GPUs at equal-or-better SLO attainment.

    ``perf`` swaps the service-rate model the whole loop plans with — pass
    ``PerfModel.from_calibration(...)`` to run on measured kernel rates
    instead of the built-in table (the ``--calibrated`` mode).
    """
    slo = SLO(ttft_seconds=2.0, tpot_seconds=0.1, attainment_target=0.95)
    perf = perf or PerfModel()
    out: Dict[str, Dict[str, float]] = {}
    for rate in rate_scales:
        specs, tspecs, peaks = _scenario_specs(rate, horizon, slo)
        traffic = generate_requests(tspecs, seed, horizon)
        for controller in controllers:
            for commit in commit_modes:
                fleet = build_fleet([(A100_80GB, n_gpus)])
                if controller == "static":
                    scaler = None
                    rho = AutoscalerConfig().target_utilization
                    run_specs = [
                        dataclasses.replace(
                            spec,
                            initial_replicas=_static_replicas(
                                spec, ts, peaks[spec.model], perf, rho
                            ),
                        )
                        for spec, ts in zip(specs, tspecs)
                    ]
                else:
                    cfg = AutoscalerConfig(mode=controller)
                    scaler = Autoscaler(cfg)
                    # Warm start at the t=0 sizing: the service was already
                    # running; what's under test is demand *tracking*.
                    run_specs = [
                        dataclasses.replace(
                            spec,
                            initial_replicas=_static_replicas(
                                spec, ts, ts.pattern.rate(0.0), perf,
                                cfg.target_utilization,
                            ),
                        )
                        for spec, ts in zip(specs, tspecs)
                    ]
                sim = DemandSimulator(
                    fleet,
                    PlacementEngine(policy, commit=commit),
                    run_specs,
                    autoscaler=scaler,
                    perf=perf,
                    autoscale_every=autoscale_every,
                    compact_every=compact_every,
                )
                stats = sim.run(traffic)
                fleet.validate()
                d = stats.as_dict()
                d["gib_moved"] = stats.bytes_moved / 2**30
                key = f"{controller}@r{rate:g}@{commit}"
                out[key] = {k: float(d[k]) for k in _AUTOSCALE_COLS}
    return out


#: columns compared between the calibrated and table PerfModel runs.
_DELTA_COLS = ("slo_attainment", "time_avg_gpus_used", "peak_gpus_used",
               "ttft_p95", "n_unserved")


def calibration_delta(
    table_rows: Dict[str, Dict[str, float]],
    cal_rows: Dict[str, Dict[str, float]],
) -> Dict[str, Dict[str, float]]:
    """Calibrated-minus-table deltas per grid row: how much the planning
    answer moves when measured kernel rates replace the hand-written
    table — the headline of the ``--calibrated`` mode."""
    out: Dict[str, Dict[str, float]] = {}
    for key, cal in cal_rows.items():
        tab = table_rows.get(key)
        if tab is None:
            continue
        out[key] = {c: cal[c] - tab[c] for c in _DELTA_COLS}
    return out


def print_calibration_delta(delta: Dict[str, Dict[str, float]]) -> None:
    log.info("\n== calibrated - table deltas (measured kernel rates vs "
             "built-in planning numbers) ==")
    width = max(30, max((len(a) for a in delta), default=0) + 2)
    log.info("controller".ljust(width)
             + "".join(c[:12].rjust(13) for c in _DELTA_COLS))
    for a, row in delta.items():
        log.info(a.ljust(width)
                 + "".join(f"{row[c]:+13.3f}" for c in _DELTA_COLS))


def print_autoscale_table(table: Dict[str, Dict[str, float]], header: str) -> None:
    log.info(f"\n== autoscale: {header} ==")
    cols = list(next(iter(table.values())).keys())
    width = max(30, max(len(a) for a in table) + 2)
    log.info("controller".ljust(width)
             + "".join(_AUTOSCALE_COLS[c][:11].rjust(12) for c in cols))
    for a, row in table.items():
        log.info(a.ljust(width) + "".join(f"{row[c]:12.3f}" for c in cols))


# ---------------------------------------------------------------------------
# faults mode (--faults): seeded chaos over the demand scenario
# ---------------------------------------------------------------------------
#: TraceStats columns surfaced per fault-grid row (clean vs faulted runs).
_FAULT_COLS = {
    "slo_attainment": "slo_attain",
    "ttft_p95": "ttft_p95",
    "time_avg_gpus_used": "avg_gpus",
    "n_requests": "requests",
    "n_unserved": "unserved",
    "n_requeued_requests": "requeued",
    "n_shed_requests": "shed",
    "n_gpu_failures": "gpu_fail",
    "n_node_drains": "drains",
    "n_fault_evictions": "evicted",
    "n_fault_recovered": "recovered",
    "n_recovery_pending": "rec_pend",
    "recovery_seconds_total": "rec_s_tot",
    "recovery_seconds_max": "rec_s_max",
    "capacity_lost_gpu_seconds": "cap_lost_s",
    "brownout_seconds": "brownout_s",
    "n_emergency_commits": "emergency",
    "disruption_minutes": "disrupt_min",
    "engine_seconds": "engine_s",
}


def _fault_specs(
    n_gpu_failures: int,
    n_drains: int,
    horizon: float,
    mttr: float,
    drain_duration: float,
) -> Tuple[FaultSpec, ...]:
    """Deterministic chaos schedule: GPU failures spread over the middle of
    the trace (so recovery is observable before the horizon) plus node
    drains at 70%.  Targets are drawn by the injector's seeded substreams."""
    specs = []
    if n_gpu_failures > 0:
        lo, hi = 0.2, 0.6
        ats = tuple(
            horizon * (lo + (hi - lo) * i / max(n_gpu_failures - 1, 1))
            for i in range(n_gpu_failures)
        )
        specs.append(FaultSpec(
            kind="gpu_failure", at=ats, duration=mttr, name="bench-gpu",
        ))
    if n_drains > 0:
        specs.append(FaultSpec(
            kind="node_drain", at=(horizon * 0.7,), count=n_drains,
            duration=drain_duration, name="bench-drain",
        ))
    return tuple(specs)


def _stats_signature(stats) -> Dict[str, float]:
    """Full TraceStats dict minus the one wall-clock field — the object the
    injector-off byte-identity contract is checked against."""
    d = stats.as_dict()
    d.pop("engine_seconds", None)
    return d


def run_faults(
    policy: str,
    n_gpus: int,
    seed: int,
    horizon: float,
    rate_scale: float,
    commit_modes: Sequence[str],
    compact_every: Optional[float],
    autoscale_every: float,
    n_gpu_failures: int,
    n_drains: int,
    fault_seed: int,
    mttr: float,
    drain_duration: float,
):
    """Clean vs faulted demand runs per commit mode over the standard
    scenario (``embed`` demoted to the best-effort brownout tier).

    Returns ``(rows, retention, byte_identity, fault_events)``:

    * rows — ``{commit}@clean`` / ``{commit}@faults`` -> ``_FAULT_COLS``;
    * retention — per commit mode, faulted/clean SLO attainment plus the
      recovery-time and capacity-lost headline numbers;
    * byte_identity — True iff a wired-but-empty ``FaultInjector(())``
      reproduces the clean trace exactly (minus wall-clock timing);
    * fault_events — the injected schedule, for reproducibility.
    """
    slo = SLO(ttft_seconds=2.0, tpot_seconds=0.1, attainment_target=0.95)
    perf = PerfModel()
    specs, tspecs, _ = _scenario_specs(rate_scale, horizon, slo)
    specs = [
        dataclasses.replace(s, best_effort=(s.model == "embed")) for s in specs
    ]
    traffic = generate_requests(tspecs, seed, horizon)
    chaos = _fault_specs(n_gpu_failures, n_drains, horizon, mttr, drain_duration)

    def _one(commit: str, faults: Optional[FaultInjector]):
        fleet = build_fleet([(A100_80GB, n_gpus)])
        cfg = AutoscalerConfig(mode="slo")
        run_specs = [
            dataclasses.replace(
                spec,
                initial_replicas=_static_replicas(
                    spec, ts, ts.pattern.rate(0.0), perf,
                    cfg.target_utilization,
                ),
            )
            for spec, ts in zip(specs, tspecs)
        ]
        sim = DemandSimulator(
            fleet,
            PlacementEngine(policy, commit=commit),
            run_specs,
            autoscaler=Autoscaler(cfg),
            perf=perf,
            autoscale_every=autoscale_every,
            compact_every=compact_every,
            faults=faults,
        )
        stats = sim.run(traffic)
        fleet.validate()
        return stats

    rows: Dict[str, Dict[str, float]] = {}
    retention: Dict[str, Dict[str, float]] = {}
    byte_identity: Optional[bool] = None
    for commit in commit_modes:
        clean = _one(commit, None)
        if byte_identity is None:
            # a wired-but-silent injector must not perturb the trace
            byte_identity = (
                _stats_signature(_one(commit, FaultInjector(())))
                == _stats_signature(clean)
            )
        faulted = _one(commit, FaultInjector(chaos, seed=fault_seed))
        for label, st in (("clean", clean), ("faults", faulted)):
            d = st.as_dict()
            rows[f"{commit}@{label}"] = {k: float(d[k]) for k in _FAULT_COLS}
        c, f = clean.slo_attainment, faulted.slo_attainment
        retention[commit] = {
            "clean_attainment": c,
            "faulted_attainment": f,
            "slo_retention": f / c if c > 0 else float("nan"),
            "recovery_seconds_max": faulted.recovery_seconds_max,
            "recovery_seconds_total": faulted.recovery_seconds_total,
            "capacity_lost_gpu_seconds": faulted.capacity_lost_gpu_seconds,
            "n_recovery_pending": float(faulted.n_recovery_pending),
            "n_requeued_requests": float(faulted.n_requeued_requests),
            "n_shed_requests": float(faulted.n_shed_requests),
        }
    events = [
        dataclasses.asdict(fe)
        for fe in FaultInjector(chaos, seed=fault_seed).schedule(
            build_fleet([(A100_80GB, n_gpus)]), horizon
        )
    ]
    return rows, retention, byte_identity, events


def print_fault_table(table: Dict[str, Dict[str, float]], header: str) -> None:
    log.info(f"\n== faults: {header} ==")
    cols = list(next(iter(table.values())).keys())
    width = max(26, max(len(a) for a in table) + 2)
    log.info("commit@run".ljust(width)
             + "".join(_FAULT_COLS[c][:11].rjust(12) for c in cols))
    for a, row in table.items():
        log.info(a.ljust(width) + "".join(f"{row[c]:12.3f}" for c in cols))


def print_fault_retention(retention: Dict[str, Dict[str, float]],
                          byte_identity: bool) -> None:
    log.info("\n== fault recovery headline (faulted vs clean) ==")
    for commit, r in retention.items():
        log.info(
            f"{commit}: SLO retention {r['slo_retention']:.3f} "
            f"({r['faulted_attainment']:.3f} / {r['clean_attainment']:.3f}), "
            f"recovery max {r['recovery_seconds_max']:.1f}s, "
            f"capacity lost {r['capacity_lost_gpu_seconds']:.1f} GPU-s, "
            f"requeued {r['n_requeued_requests']:.0f}, "
            f"shed {r['n_shed_requests']:.0f}"
        )
    log.info(f"injector-off byte identity: {byte_identity}")


# ---------------------------------------------------------------------------
# fleet-scale mode (--fleet-scale): scalar path vs vectorized fabric
# ---------------------------------------------------------------------------
#: metrics surfaced in the fleet-scale comparison (the acceptance metrics:
#: GPUs used + wastage + fragmentation + pending).
_SCALE_METRICS = (
    "n_gpus", "compute_wastage", "memory_wastage", "fragmentation", "n_pending",
)


def _deploy_once(tc: TestCase, policy: str, fabric: str) -> Dict[str, float]:
    st = tc.initial.clone()
    eng = PlacementEngine(policy, fabric=fabric)
    res = eng.deploy(st, tc.new_workloads)
    st.validate()
    all_wl = list(tc.initial.workloads.values()) + list(tc.new_workloads)
    m = metrics.evaluate(st, tc.initial, all_wl)
    out = {k: float(getattr(m, k)) for k in _SCALE_METRICS}
    out["seconds"] = res.seconds
    return out


def run_fleet_scale(
    n_gpus: int, seed: int, horizon: float
) -> Dict[str, Dict[str, float]]:
    """One fleet size: deploys (scalar vs fabric) + a short online trace.

    The fabric deploy is run twice and the warm timing reported (the first
    call pays one-off jit compilation for the fleet shape; ``cold_seconds``
    is kept in the JSON for honesty).
    """
    tc = generate_test_case(seed, n_gpus=n_gpus)
    rows: Dict[str, Dict[str, float]] = {}
    for policy in ("first_fit", "rule_based"):
        scalar = _deploy_once(tc, policy, fabric="off")
        cold = _deploy_once(tc, policy, fabric="on")
        warm = _deploy_once(tc, policy, fabric="on")
        assert all(
            warm[k] == scalar[k] for k in _SCALE_METRICS
        ), f"fabric parity broken for {policy} @ {n_gpus}"
        row = dict(warm)
        row["scalar_seconds"] = scalar["seconds"]
        row["cold_seconds"] = cold["seconds"]
        row["speedup"] = scalar["seconds"] / max(warm["seconds"], 1e-9)
        rows[policy] = row
    frag = _deploy_once(tc, "frag_aware", fabric="on")
    frag["scalar_seconds"] = float("nan")
    frag["cold_seconds"] = frag["seconds"]
    frag["speedup"] = float("nan")
    rows["frag_aware"] = frag

    # Short online trace over the same fleet size (arrival rate scaled so
    # steady-state load covers roughly half the fleet); compaction off — this
    # measures deploy latency and GPUs-used/wastage per policy at scale.
    for policy in ("first_fit", "rule_based", "frag_aware"):
        fleet = build_fleet([(A100_80GB, n_gpus)])
        trace = generate_trace(
            seed, fleet, horizon=horizon, arrival_rate=max(1.0, n_gpus / 8.0),
            mean_lifetime=horizon * 0.6,
        )
        stats = OnlineSimulator(fleet, PlacementEngine(policy)).run(trace)
        fleet.validate()
        rows[policy]["trace_avg_gpus"] = stats.time_avg_gpus_used
        rows[policy]["trace_avg_cwaste"] = stats.time_avg_compute_waste
        rows[policy]["trace_engine_seconds"] = stats.engine_seconds
    return rows


def print_fleet_scale(n_gpus: int, rows: Dict[str, Dict[str, float]]) -> None:
    log.info(f"\n== fleet-scale @ {n_gpus} GPUs (deploy; fabric vs scalar) ==")
    cols = (
        "scalar_seconds", "seconds", "speedup", "n_gpus", "compute_wastage",
        "memory_wastage", "fragmentation", "n_pending",
        "trace_avg_gpus", "trace_avg_cwaste", "trace_engine_seconds",
    )
    short = {
        "scalar_seconds": "scalar_s", "seconds": "fabric_s",
        "compute_wastage": "cwaste", "memory_wastage": "mwaste",
        "fragmentation": "frag", "trace_avg_gpus": "tr_gpus",
        "trace_avg_cwaste": "tr_cwaste", "trace_engine_seconds": "tr_eng_s",
    }
    log.info("policy".ljust(12) + "".join(short.get(c, c)[:10].rjust(11) for c in cols))
    for a, row in rows.items():
        log.info(a.ljust(12) + "".join(f"{row.get(c, float('nan')):11.3f}" for c in cols))


def write_json(path: str, report: Dict, schema: str = "placement_bench/v1") -> None:
    """Write via the shared strict-JSON report writer (``obs.write_report``):
    sections merge into an existing report of the same schema family (so a
    ``--trace`` run and an ``--autoscale`` run can share one file) and
    non-finite floats serialize as ``null``, never ``NaN``.  ``--faults``
    runs write a ``failures_bench/v1`` report instead."""
    if obs.write_report(path, report, schema):
        log.info(f"wrote {path}")


# ---------------------------------------------------------------------------
# telemetry plumbing (--telemetry)
# ---------------------------------------------------------------------------
def planner_latency_section(tel: obs.Telemetry) -> Dict[str, Dict[str, float]]:
    """Per-verb planner-latency percentiles from the live registry:
    {"verb@policy": {count, p50_s, p95_s, p99_s, total_s}}."""
    out: Dict[str, Dict[str, float]] = {}
    for inst in tel.metrics.families().get("planner_latency_seconds", []):
        labels = dict(inst.labels)
        key = f"{labels.get('verb', '?')}@{labels.get('policy', '?')}"
        pct = inst.percentiles((50, 95, 99))
        out[key] = {
            "count": float(inst.count),
            "total_s": inst.sum,
            "p50_s": pct["p50"],
            "p95_s": pct["p95"],
            "p99_s": pct["p99"],
        }
    return out


def dump_telemetry(tel: obs.Telemetry, prefix: str) -> None:
    """Write the run's spans/events as JSONL and the registry as Prometheus
    text exposition, under ``{prefix}_spans.jsonl`` / ``{prefix}_metrics.prom``."""
    spans_path = f"{prefix}_spans.jsonl"
    prom_path = f"{prefix}_metrics.prom"
    n = obs.write_jsonl(tel.tracer.records(), spans_path)
    with open(prom_path, "w") as f:
        f.write(obs.prometheus_text(tel.metrics))
    log.info(f"wrote {spans_path} ({n} records) and {prom_path}")
    log.info(f"render with: python -m repro.obs.report {spans_path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="all",
                    choices=["initial", "compaction", "reconfiguration", "all"])
    ap.add_argument("--gpus", type=int, nargs="+", default=[8, 80])
    ap.add_argument("--cases", type=int, default=100)
    ap.add_argument("--mip-cases", type=int, default=None,
                    help="cap test cases for MIP approaches (big clusters)")
    ap.add_argument("--time-limit", type=float, default=30.0)
    # online trace mode
    ap.add_argument("--trace", action="store_true",
                    help="online arrival/departure trace over a mixed fleet")
    ap.add_argument("--policies", nargs="+",
                    default=["first_fit", "load_balanced", "rule_based"])
    ap.add_argument("--tpu-pods", type=int, default=2,
                    help="TPU v5e pods to add next to the --gpus A100s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon", type=float, default=200.0)
    ap.add_argument("--arrival-rate", type=float, default=1.0)
    ap.add_argument("--mean-lifetime", type=float, default=40.0)
    ap.add_argument("--compact-every", type=float, default=25.0)
    ap.add_argument("--migration-budget", type=int, default=None)
    ap.add_argument("--commit", nargs="+", default=["always"],
                    choices=["always", "net-positive", "budgeted"],
                    help="CommitPolicy mode(s); several = side-by-side rows "
                    "per policy (plan/score/commit control plane)")
    ap.add_argument("--reconfigure-every", type=float, default=None,
                    help="periodic maintenance repack (Sec 2.3.3) in the "
                    "online trace; the verb the CommitPolicy keeps honest")
    # autoscale mode
    ap.add_argument("--autoscale", action="store_true",
                    help="demand-driven mode: request traffic + replica "
                    "controller closing the loop into the engine")
    ap.add_argument("--rate-scale", type=float, nargs="+", default=[1.0],
                    help="multipliers on the demand scenario's base rates "
                    "(several = arrival-rate sweep)")
    ap.add_argument("--controller", nargs="+", default=["slo", "static"],
                    choices=["slo", "target-utilization", "static"],
                    help="autoscaler mode(s); 'static' = peak-provisioned "
                    "fixed replicas (the over-provisioning baseline)")
    ap.add_argument("--autoscale-every", type=float, default=5.0,
                    help="control-tick period (simulated seconds)")
    ap.add_argument("--calibrated", default=None, metavar="CALIBRATION.json",
                    help="run the autoscale grid a second time on a "
                    "measured PerfModel loaded from this calibration "
                    "artifact (benchmarks/calibrate.py output); rows gain "
                    "an @cal variant and the report a calibration_delta "
                    "section (calibrated-minus-table attainment/GPUs)")
    # faults mode
    ap.add_argument("--faults", action="store_true",
                    help="seeded chaos mode: clean-vs-faulted demand runs "
                    "per commit mode; emits BENCH_failures.json "
                    "(failures_bench/v1) with SLO retention, "
                    "recovery-time-to-full-capacity, and "
                    "capacity-lost-GPU-seconds")
    ap.add_argument("--gpu-failures", type=int, default=3,
                    help="GPU hard failures injected mid-trace")
    ap.add_argument("--node-drains", type=int, default=1,
                    help="simultaneous node drains injected at 70%% horizon")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the injector's target-selection streams")
    ap.add_argument("--fault-mttr", type=float, default=None,
                    help="repair time per GPU failure (default 15%% horizon)")
    ap.add_argument("--drain-duration", type=float, default=None,
                    help="drain length (default 20%% horizon)")
    # fleet-scale mode
    ap.add_argument("--fleet-scale", type=int, nargs="+", default=None,
                    metavar="N", help="fleet sizes for the fabric-vs-scalar "
                    "comparison (e.g. 256 1024 4096)")
    ap.add_argument("--fleet-horizon", type=float, default=20.0,
                    help="trace horizon per fleet-scale size")
    ap.add_argument("--json", default="BENCH_placement.json",
                    help="machine-readable output path ('' disables)")
    # observability
    ap.add_argument("--telemetry", action="store_true",
                    help="enable repro.obs: span-trace engine verbs, add "
                    "planner-latency p50/p95/p99 to the JSON report, and "
                    "dump spans (JSONL) + metrics (Prometheus text)")
    ap.add_argument("--telemetry-prefix", default="TELEMETRY",
                    help="output prefix for the spans/metrics dumps")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="debug logging (timings, progress) on stderr")
    args = ap.parse_args()

    logging.basicConfig(
        stream=sys.stderr,
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(message)s",
    )

    tel: Optional[obs.Telemetry] = None
    if args.telemetry:
        tel = obs.enable()

    report: Dict = {"args": {k: v for k, v in vars(args).items() if k != "json"}}
    # contended-host guard: timings next to a stale pytest/bench are suspect
    report["host"] = obs.host_snapshot()

    def _finish(rep: Dict, schema: str = "placement_bench/v1") -> None:
        if tel is not None:
            rep["planner_latency"] = planner_latency_section(tel)
            dump_telemetry(tel, args.telemetry_prefix)
        write_json(args.json, rep, schema)

    if args.faults:
        n_a100 = args.gpus[0]
        if args.json == ap.get_default("json"):
            args.json = "BENCH_failures.json"  # own artifact, own schema
        mttr = (args.fault_mttr if args.fault_mttr is not None
                else args.horizon * 0.15)
        drain_dur = (args.drain_duration if args.drain_duration is not None
                     else args.horizon * 0.2)
        t0 = time.time()
        rows, retention, identity, events = run_faults(
            args.policies[0], n_a100, args.seed, args.horizon,
            args.rate_scale[0], args.commit,
            args.compact_every if args.compact_every > 0 else None,
            args.autoscale_every,
            args.gpu_failures, args.node_drains, args.fault_seed,
            mttr, drain_dur,
        )
        print_fault_table(
            rows,
            f"{n_a100}x A100, horizon {args.horizon}, "
            f"{args.gpu_failures} GPU failures + {args.node_drains} drain(s)",
        )
        print_fault_retention(retention, identity)
        log.debug(f"   ({time.time() - t0:.0f}s)")
        report["faults"] = {
            "rows": rows,
            "retention": retention,
            "fault_byte_identity": identity,
            "fault_events": events,
        }
        _finish(report, schema="failures_bench/v1")
        return

    if args.fleet_scale:
        report["fleet_scale"] = {}
        for n in args.fleet_scale:
            t0 = time.time()
            rows = run_fleet_scale(n, args.seed, args.fleet_horizon)
            print_fleet_scale(n, rows)
            log.debug(f"   ({time.time() - t0:.0f}s)")
            report["fleet_scale"][str(n)] = rows
        _finish(report)
        return

    if args.autoscale:
        n_a100 = args.gpus[0]
        t0 = time.time()
        grid_args = (
            args.policies[0], n_a100, args.seed, args.horizon,
            args.rate_scale, args.controller, args.commit,
            args.compact_every if args.compact_every > 0 else None,
            args.autoscale_every,
        )
        table = run_autoscale(*grid_args)
        print_autoscale_table(
            table,
            f"{n_a100}x A100, horizon {args.horizon}, "
            f"policy {args.policies[0]}",
        )
        if args.calibrated:
            perf_cal = PerfModel.from_calibration(args.calibrated)
            whole = perf_cal.device_throughput(A100_80GB)
            log.info(
                f"\ncalibrated PerfModel from {args.calibrated}: "
                f"prefill {whole.prefill_tokens_per_s:.0f} tok/s, decode "
                f"{whole.decode_tokens_per_s:.0f} tok/s, "
                f"e={perf_cal.parallel_efficiency:.3f}"
            )
            cal_table = run_autoscale(*grid_args, perf=perf_cal)
            print_autoscale_table(
                cal_table, f"CALIBRATED rates, {n_a100}x A100"
            )
            delta = calibration_delta(table, cal_table)
            print_calibration_delta(delta)
            table = dict(table)
            table.update({f"{k}@cal": v for k, v in cal_table.items()})
            report["calibration_delta"] = delta
            report["calibration_source"] = args.calibrated
        log.debug(f"   ({time.time() - t0:.0f}s)")
        report["autoscale"] = table
        _finish(report)
        return

    if args.trace:
        n_a100 = args.gpus[0]
        t0 = time.time()
        table = run_trace(
            args.policies, n_a100, args.tpu_pods, args.seed, args.horizon,
            args.arrival_rate, args.mean_lifetime,
            args.compact_every if args.compact_every > 0 else None,
            args.migration_budget, args.time_limit,
            commit_modes=args.commit,
            reconfigure_every=args.reconfigure_every,
        )
        print_trace_table(
            table,
            f"{n_a100}x A100 + {args.tpu_pods}x TPU pod, horizon {args.horizon}",
        )
        log.debug(f"   ({time.time() - t0:.0f}s)")
        report["trace"] = table
        _finish(report)
        return

    cases = (
        ["initial", "compaction", "reconfiguration"]
        if args.case == "all" else [args.case]
    )
    report["snapshot"] = {}
    for case in cases:
        for g in args.gpus:
            t0 = time.time()
            table = run_case(case, g, args.cases, args.time_limit, args.mip_cases)
            print_table(case, g, table)
            log.debug(f"   ({time.time() - t0:.0f}s)")
            report["snapshot"][f"{case}@{g}"] = table
    _finish(report)


if __name__ == "__main__":
    enable_compile_cache()
    main()

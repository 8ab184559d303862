"""Telemetry subsystem tests (repro/obs).

Covers the PR-5 observability guarantees:

- telemetry is *inert*: a seeded simulation run with telemetry enabled is
  byte-identical (TraceStats + final placements) to a telemetry-off run;
- every committed plan verb produces a complete plan/score/commit span
  tree (and rejected plans a rollback child);
- ``Histogram.percentile`` matches ``numpy.percentile`` linear
  interpolation on the raw reservoir;
- exporters: Prometheus text exposition shape, strict (NaN-free) JSONL
  round-trip, and the ``repro.obs.report`` renderer;
- live spans land in a profiler trace as ``repro/<name>``; the replica
  engine, fabric sync and compaction spans and counters are where the work
  is, and served tokens and compaction layouts are the same on and off.
"""
import json
import math
import os
import subprocess
import sys
import time
from glob import glob
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core.engine import CommitPolicy, PlacementEngine
from repro.core.events import OnlineSimulator, build_fleet, generate_trace
from repro.core.profiles import A100_80GB
from repro.core.simulator import generate_test_case
from repro.core.state import ClusterState, Workload
from repro.core.tpu_profiles import TPU_V5E_POD
from repro.obs import report

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Never leak an enabled Telemetry into other tests."""
    yield
    obs.disable()


def _snapshot(state: ClusterState):
    return sorted(
        (gid, p.wid, p.profile_id, p.index)
        for gid, g in state.gpus.items()
        for p in g.placements
    )


def _run_trace(seed: int = 11):
    fleet = build_fleet([(A100_80GB, 6), (TPU_V5E_POD, 1)])
    trace = generate_trace(
        seed, fleet, horizon=80.0, arrival_rate=0.5, mean_lifetime=30.0
    )
    sim = OnlineSimulator(
        fleet, PlacementEngine("rule_based"), compact_every=20.0
    )
    stats = sim.run(trace)
    return stats, _snapshot(fleet)


class TestTelemetryIsInert:
    def test_enabled_run_is_byte_identical_to_disabled(self):
        obs.disable()
        stats_off, snap_off = _run_trace()
        obs.enable()
        stats_on, snap_on = _run_trace()

        d_off, d_on = stats_off.as_dict(), stats_on.as_dict()
        # wall-clock engine time is inherently nondeterministic; everything
        # else must match to the byte.
        for d in (d_off, d_on):
            d.pop("engine_seconds")
        assert d_on == d_off
        assert snap_on == snap_off
        assert json.dumps(obs.sanitize_json(d_on), sort_keys=True) == \
            json.dumps(obs.sanitize_json(d_off), sort_keys=True)

    def test_noop_tracer_holds_no_shared_mutable_state(self):
        tr = obs.NoopTracer()
        assert tr.spans == () and tr.events == ()  # immutable, never a shared list

    def test_disabled_telemetry_records_nothing(self):
        obs.disable()
        tel = obs.get_telemetry()
        with tel.tracer.span("deploy") as sp:
            sp.set(foo=1)
        tel.metrics.counter("c", "help").inc()
        assert tel.tracer.records() == []
        assert tel.metrics.families() == {}
        assert not tel.enabled


class TestSpanTrees:
    def _state(self):
        st = ClusterState.homogeneous(3)
        for wid, pid, gid, idx in [
            ("w1", 15, "gpu0", 0), ("w2", 15, "gpu1", 0), ("w3", 19, "gpu2", 0),
        ]:
            st.add_workload(Workload(wid=wid, profile_id=pid))
            st.place(wid, gid, idx)
        return st

    def test_committed_compact_has_plan_score_commit_children(self):
        tel = obs.enable()
        res = PlacementEngine("rule_based").compact(self._state())
        assert res.committed
        roots = tel.tracer.find(name="compact")
        assert len(roots) == 1
        root = roots[0]
        assert root.parent_id is None
        children = {c.name for c in tel.tracer.children_of(root)}
        assert {"plan", "score", "commit"} <= children
        for c in tel.tracer.children_of(root):
            assert c.parent_id == root.span_id
            assert c.trace_id == root.trace_id
        assert root.attrs["committed"] is True
        assert root.attrs["n_moves"] == res.plan.n_moves

    def test_snapshot_plan_score_commit_partition_a_compaction(self):
        tel = obs.enable()
        PlacementEngine("rule_based").compact(self._state())
        root = tel.tracer.find(name="compact")[0]
        kids = sorted(tel.tracer.children_of(root), key=lambda c: c.start_perf)
        assert [c.name for c in kids] == ["snapshot", "plan", "score", "commit"]
        assert sum(c.duration for c in kids) <= root.duration

    def test_rejected_plan_has_rollback_child_and_term(self):
        tel = obs.enable()
        engine = PlacementEngine(
            "rule_based", commit=CommitPolicy(move_budget=0)
        )
        res = engine.compact(self._state())
        assert not res.committed
        root = tel.tracer.find(name="compact")[0]
        children = {c.name for c in tel.tracer.children_of(root)}
        assert "rollback" in children and "commit" not in children
        assert root.attrs["term"] == res.decision.term == "moves"
        assert res.decision.shortfall >= 1.0

    def test_commit_decision_terms(self):
        res = PlacementEngine("rule_based").compact(self._state())
        gains, cost = res.gains, res.cost
        assert cost.n_moves > 0
        always = CommitPolicy(mode="always").decide(gains, cost)
        assert always.commit and always.term == "always"
        assert always.shortfall == 0.0
        moves = CommitPolicy(move_budget=0).decide(gains, cost)
        assert not moves.commit and moves.term == "moves"
        assert moves.shortfall == pytest.approx(cost.n_moves)
        byts = CommitPolicy(mode="budgeted", bytes_budget=1).decide(gains, cost)
        assert not byts.commit and byts.term == "bytes"
        assert byts.shortfall == pytest.approx(cost.total_bytes - 1)


class TestHistogram:
    def test_percentile_matches_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.exponential(0.05, size=500)
        h = obs.Histogram("h", "help", labels=())
        for v in vals:
            h.observe(float(v))
        for q in (50.0, 90.0, 95.0, 99.0, 100.0):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(vals, q)), rel=1e-9
            )

    def test_cumulative_buckets_and_count(self):
        h = obs.Histogram("h", "help", labels=(), buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        cum = h.cumulative_buckets()
        assert cum == [(0.1, 1), (1.0, 2), (math.inf, 3)]
        assert h.count == 3 and h.sum == pytest.approx(5.55)


class TestExporters:
    def test_prometheus_text_shape(self):
        tel = obs.Telemetry.live()
        tel.metrics.counter(
            "plans_committed_total", "plans committed", labels={"verb": "compact"}
        ).inc(3)
        tel.metrics.gauge("gpus_used", "gpus in use").set(7)
        tel.metrics.histogram("latency_seconds", "verb latency").observe(0.2)
        text = obs.prometheus_text(tel.metrics)
        assert "# TYPE repro_plans_committed_total counter" in text
        assert 'repro_plans_committed_total{verb="compact"} 3' in text
        assert "repro_gpus_used 7" in text
        assert 'repro_latency_seconds_bucket{le="+Inf"} 1' in text
        assert "repro_latency_seconds_count 1" in text
        assert text.endswith("\n")

    def test_jsonl_round_trip_is_strict(self, tmp_path):
        tel = obs.Telemetry.live()
        with tel.tracer.span("deploy") as sp:
            sp.set(policy="rule_based", score=float("nan"))
        dest = tmp_path / "spans.jsonl"
        n = obs.write_jsonl(tel.tracer.records(), dest)
        assert n == 1

        def _reject(x):
            raise ValueError(f"non-strict JSON constant {x!r}")

        [rec] = [
            json.loads(line, parse_constant=_reject)
            for line in dest.read_text().splitlines()
        ]
        assert rec["name"] == "deploy"
        assert rec["attrs"]["score"] is None  # NaN sanitized to null
        assert list(obs.iter_jsonl(dest)) == [rec]

    def test_sanitize_json_scrubs_non_finite(self):
        out = obs.sanitize_json(
            {"a": float("inf"), "b": [float("-inf"), 1.5], "c": {"d": math.nan}}
        )
        assert out == {"a": None, "b": [None, 1.5], "c": {"d": None}}
        json.dumps(out, allow_nan=False)  # must not raise


class TestReport:
    def test_report_renders_from_generated_spans(self, tmp_path, capsys):
        tel = obs.enable()
        _run_trace(seed=3)
        dest = tmp_path / "spans.jsonl"
        obs.write_jsonl(tel.tracer.records(), dest)
        report.main([str(dest), "--width", "60"])
        out = capsys.readouterr().out
        assert "per-span latency" in out
        assert "deploy" in out
        spans, _events = report.load_records(str(dest))
        rows = report.latency_table(spans)
        deploy = next(r for r in rows if r["name"] == "deploy")
        assert deploy["count"] > 0
        assert deploy["p50_s"] <= deploy["p95_s"] <= deploy["p99_s"]

    def test_html_timeline(self, tmp_path):
        tel = obs.enable()
        _run_trace(seed=3)
        dest = tmp_path / "spans.jsonl"
        obs.write_jsonl(tel.tracer.records(), dest)
        html = tmp_path / "report.html"
        report.main([str(dest), "--html", str(html)])
        text = html.read_text()
        assert text.lstrip().lower().startswith("<!doctype html>")
        assert "deploy" in text


def _xplane_events(trace_dir):
    """(name, duration s) of every event on the host planes of a trace."""
    [path] = glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.duration_ns * 1e-9) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events]


class TestProfilerClock:
    def test_live_span_lands_on_the_host_plane(self, tmp_path):
        tel = obs.enable()
        jax.profiler.start_trace(str(tmp_path))
        t0 = time.perf_counter()
        with tel.tracer.span("probe") as sp:
            time.sleep(0.02)
        jax.profiler.stop_trace()
        assert t0 <= sp.start_perf <= t0 + sp.duration
        assert sp.as_dict()["start_perf"] == sp.start_perf
        got = [d for name, d in _xplane_events(tmp_path) if name == "repro/probe"]
        assert len(got) == 1 and 0.02 <= got[0] <= sp.duration + 0.01

    def test_obs_imports_and_runs_disabled_without_jax(self):
        code = ("import sys; sys.modules['jax'] = None\n"
                "from repro import obs\n"
                "with obs.get_telemetry().tracer.span('x') as sp:\n"
                "    sp.set(a=1)\n"
                "print('ok')")
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=60,
                             env={**os.environ, "PYTHONPATH": SRC})
        assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr

    def test_disabled_tracer_annotates_nothing(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        with obs.get_telemetry().tracer.span("probe"):
            time.sleep(0.005)
        jax.profiler.stop_trace()
        assert not any(n.startswith("repro/") for n, _ in _xplane_events(tmp_path))


def _serve(prompts):
    from repro.configs import get_config, reduced
    from repro.models import bundle
    from repro.serving import Engine, EngineConfig, Request

    mb = bundle(reduced(get_config("smollm-135m")))
    eng = Engine(mb, mb.init(jax.random.key(0)), EngineConfig(max_slots=2, max_len=64))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=3 + i))
    return {c.rid: c.tokens for c in eng.run()}


class TestReplicaSpans:
    PROMPTS = [[5, 9, 2], [7] * 11, [3, 1, 4, 1, 5], [8, 8]]

    def test_spans_per_request_and_per_round_tokens_unchanged(self):
        off = _serve(self.PROMPTS)
        tel = obs.enable()
        on = _serve(self.PROMPTS)
        obs.disable()
        assert on == off and len(on) == len(self.PROMPTS)
        tr = tel.tracer
        rids = {f"r{i}" for i in range(len(self.PROMPTS))}
        assert sorted(s.attrs["rid"] for s in tr.find("replica.submit")) == sorted(rids)
        prefills = tr.find("replica.prefill")
        assert sorted(s.attrs["rid"] for s in prefills) == sorted(rids)
        for s in prefills:
            plen = len(self.PROMPTS[int(s.attrs["rid"][1:])])
            assert s.attrs["prompt_len"] == plen and s.attrs["bucket"] >= plen
            assert [c.name for c in tr.children_of(s)] == ["replica.insert"]
        by_rid = {s.attrs["rid"]: s for s in tr.find("replica.submit")}
        assert all(p.start_perf > by_rid[p.attrs["rid"]].start_perf for p in prefills)
        steps = {s.span_id for s in tr.find("replica.step")}
        decodes = tr.find("replica.decode")
        assert decodes and all(d.parent_id in steps for d in decodes)
        assert all(p.parent_id in steps for p in prefills)
        for d in decodes:
            kids = sorted(tr.children_of(d), key=lambda c: c.start_perf)
            assert [c.name for c in kids] == [
                "replica.decode.prepare", "replica.decode.fetch", "replica.decode.commit"]
            assert 1 <= d.attrs["n_active"] <= 2


class TestPlacementCounters:
    def test_compaction_counts_and_layout_unchanged(self):
        case = generate_test_case(3, n_gpus=24)
        off = case.initial.clone()
        PlacementEngine("rule_based").compact(off)
        tel = obs.enable()
        on = case.initial.clone()
        PlacementEngine("rule_based").compact(on)
        obs.disable()
        assert _snapshot(on) == _snapshot(off)
        [plan] = [s for s in tel.tracer.find("plan")
                  if tel.tracer.find("compact")[0].span_id == s.parent_id]
        a = plan.attrs
        assert a["passes"] >= 1 and a["vacate_attempts"] >= a["vacated"] >= 1
        freed = len(case.initial.used_gpus()) - len(on.used_gpus())
        assert a["vacated"] - a["borrow_fallbacks"] <= freed <= a["vacated"]
        assert a["precheck_skips"] >= 0

    def test_fabric_sync_span_counts_refreshed_rows(self):
        case = generate_test_case(5, n_gpus=16)
        state, (first, *rest) = case.initial, case.new_workloads
        eng = PlacementEngine("rule_based", fabric="on")
        eng.deploy(state, [first])  # builds the mirror: nothing to sync
        tel = obs.enable()
        eng.deploy(state, rest[:1])
        syncs = tel.tracer.find("fabric.sync")
        assert len(syncs) == 1 and syncs[0].attrs["rows"] == 1
        deploy = tel.tracer.find("deploy")[0]
        assert syncs[0].trace_id == deploy.span_id

"""Driver for the placement control plane under churn: one ``ClusterState``
of the configuration's fleet, driven through ``PlacementEngine.deploy``,
``ClusterState.remove`` and ``PlacementEngine.compact`` in trace order.

The state is loaded fresh, as a control plane that starts from a snapshot
holds it: the engine builds its fabric mirror (the one sweep on the device)
at the window's first deploy.  Set-up compiles that sweep on a copy.

A deploy's answer (where each new workload landed, or that it was left
pending) is read where it can no longer have changed: when the workload
departs, just before a compaction, or at the end.  The check replays the
same events through the configuration's plain reference and counts the
answers and post-compaction layouts that differ.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import traffic_gen
from harness import load_module

from repro.core.engine import PlacementEngine
from repro.core.profiles import A100_80GB
from repro.core.state import ClusterState, GPUState, Workload

DEVICES = {"A100-80GB": A100_80GB}
#: the device works once, at the first deploy after the compaction that
#: opens the window: trace the whole window (host spans keep it small)
TRACE_SPAN = (0.0, float("inf"))
Layout = Dict[str, Tuple[Tuple[str, int, int], ...]]


def layout(state: ClusterState) -> Layout:
    return {gid: tuple(sorted((p.wid, p.profile_id, p.index) for p in g.placements))
            for gid, g in state.gpus.items() if g.placements}


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: list, root):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.ref = load_module(root / cfg["reference"])
        fleet, self.wids = self.ref.initial_fleet(cfg)
        device = DEVICES[cfg["device"]["name"]]
        gids = [cfg["gid_format"].format(i=i) for i in range(cfg["n_gpus"])]
        self.state = ClusterState(gpus={g: GPUState(g, device) for g in gids})
        for wid in self.wids:
            gid = fleet.where[wid]
            pid = fleet.pid_of[wid]
            idx = next(pl[2] for pl in fleet.gpus[gid].placements if pl[0] == wid)
            self.state.add_workload(Workload(wid=wid, profile_id=pid))
            self.state.gpus[gid].place(wid, pid, idx)
        self.engine = PlacementEngine(cfg["policy"], commit=cfg["commit"])
        self._warm()

    def _warm(self) -> None:
        """One deploy of each profile of the mix on a copy of the fleet:
        compiles the fabric's sweeps for the fleet's shape and leaves the
        state itself as loaded."""
        copy = self.state.clone()
        for pid in self.mix["profile_pool"]:
            self.engine.deploy(copy, [Workload(wid=f"warm{pid}", profile_id=pid)])

    def window(self, seconds: float, span, tracer) -> dict:
        events = traffic_gen.churn(self.mix, self.seed, seconds,
                                   self.cfg["compact_first_s"],
                                   self.cfg["compact_period_s"])
        self.events = events
        live: List[str] = list(self.wids)
        answers: Dict[str, Optional[Tuple[str, int]]] = {}
        unread: List[str] = []  # placed, answer not read yet
        snapshots: List[Layout] = []
        place_s, deploy_s, compact_s, used = [], [], [], []
        late = []
        state, engine = self.state, self.engine

        def read_answers(lay: Layout) -> None:
            where = {pl[0]: (gid, pl[2]) for gid, pls in lay.items() for pl in pls}
            for wid in unread:
                answers[wid] = where[wid]
            unread.clear()

        t0 = time.perf_counter()
        for ev in events:
            now = time.perf_counter() - t0
            tracer.tick(now)
            if ev.due_s > now:
                with span("wait"):
                    time.sleep(ev.due_s - now)
            late.append(max(0.0, time.perf_counter() - t0 - ev.due_s))
            if ev.kind == "arrive":
                ws = [Workload(wid=w, profile_id=p) for w, p in ev.workloads]
                ta = time.perf_counter()
                with span("deploy"):
                    res = engine.deploy(state, ws)
                tb = time.perf_counter()
                deploy_s.append(tb - ta)
                place_s.append(tb - t0 - ev.due_s)
                pending = {w.wid for w in res.pending}
                for w in ws:
                    if w.wid in pending:
                        answers[w.wid] = None
                    else:
                        unread.append(w.wid)
                        live.append(w.wid)
            elif ev.kind == "depart":
                wid = live.pop(traffic_gen.pick_index(ev.pick, len(live)))
                with span("depart"):
                    gid, pl = state.placement_of(wid)
                    state.remove(wid, gid)
                if wid in unread:
                    unread.remove(wid)
                    answers[wid] = (gid, pl.index)
            else:
                with span("observe"):
                    read_answers(layout(state))
                ta = time.perf_counter()
                with span("compact"):
                    engine.compact(state)
                compact_s.append(time.perf_counter() - ta)
                with span("observe"):
                    snapshots.append(layout(state))
            used.append(sum(1 for g in state.gpus.values() if g.placements))
        final = layout(state)
        read_answers(final)
        self.answers, self.snapshots, self.final = answers, snapshots, final
        return {
            "kind": "placement",
            "seconds": seconds,
            "place_s": place_s,
            "deploy_s": deploy_s,
            "compact_s": compact_s,
            "gpus_used": used,
            "late_s": late,
            "attempted": len(answers),
            "failed": sum(a is None for a in answers.values()),
        }

    def check(self, rec: dict) -> Dict[str, tuple]:
        try:
            self.state.validate()
            invalid = 0
        except ValueError:
            invalid = 1
        want = replay(self.ref.initial_fleet(self.cfg), self.events)
        wrong = mismatches(want, self.answers, self.snapshots, self.final)
        return {"answers_wrong": (wrong, 0), "invalid_layouts": (invalid, 0)}


def replay(start, events):
    """Answers, post-compaction layouts and final layout of ``events`` on a
    reference fleet ``start = (fleet, initial workload ids)``."""
    fleet, wids = start
    live = list(wids)
    answers, snapshots = {}, []
    for ev in events:
        if ev.kind == "arrive":
            got = fleet.deploy(ev.workloads)
            for wid, _ in ev.workloads:
                answers[wid] = got[wid]
                if got[wid] is not None:
                    live.append(wid)
        elif ev.kind == "depart":
            fleet.remove(live.pop(traffic_gen.pick_index(ev.pick, len(live))))
        else:
            fleet.compact()
            snapshots.append(fleet.layout())
    return answers, snapshots, fleet.layout()


def mismatches(want, answers, snapshots, final) -> int:
    """Deploy answers, post-compaction layouts and the final layout that
    differ from the reference's ``want = replay(...)``."""
    w_answers, w_snaps, w_final = want
    wrong = sum(answers.get(wid, "missing") != spot for wid, spot in w_answers.items())
    wrong += len(answers) != len(w_answers) or len(snapshots) != len(w_snaps)
    wrong += sum(a != b for a, b in zip(snapshots, w_snaps))
    return wrong + (final != w_final)

"""Plain reference of the fleet-a100-1024 deployment: MIG geometry, the
paper's Sec-5.1 fleet generator and the Sec-4.2 rule-based verbs.

Written from the paper (arXiv:2409.06646, Table 1, Sec 4.2, Sec 5.1) and
the configuration file alone; it imports nothing of the system under test.
A GPU is a bitmask of occupied memory positions plus its placements in the
order they were made.  ``Fleet(..., numeric_indexes=True)`` is the control:
it breaks the Table-1 index preference that the configuration guarantees.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Spot = Optional[Tuple[str, int]]


class Gpu:
    __slots__ = ("gid", "placements", "occ", "mem", "cmp", "me")

    def __init__(self, gid: str):
        self.gid = gid
        self.placements: List[Tuple[str, int, int]] = []  # (wid, pid, index)
        self.occ = 0  # bit i set: memory position i is taken
        self.mem = self.cmp = self.me = 0


class Fleet:
    def __init__(self, cfg: dict, numeric_indexes: bool = False):
        dev = cfg["device"]
        self.n_mem = dev["memory_slices"]
        self.n_gpu = dev["gpu_slices"]
        self.max_me = dev["max_media_extensions"]
        self.prof = {p["id"]: p for p in dev["profiles"]}
        # Table 1 lists profiles from the largest down: that order is "size"
        self.rank = {p["id"]: i for i, p in enumerate(dev["profiles"])}
        self.order = {
            pid: (sorted(p["indexes"]) if numeric_indexes else list(p["indexes"]))
            for pid, p in self.prof.items()
        }
        gids = [cfg["gid_format"].format(i=i) for i in range(cfg["n_gpus"])]
        self.gids = sorted(gids)
        self.gpus: Dict[str, Gpu] = {g: Gpu(g) for g in gids}
        self.where: Dict[str, str] = {}  # wid -> gid
        self.pid_of: Dict[str, int] = {}

    # ---- geometry --------------------------------------------------------
    def _span(self, pid: int, idx: int) -> int:
        return ((1 << self.prof[pid]["memory"]) - 1) << idx

    def fits(self, g: Gpu, pid: int, idx: int, occ: Optional[int] = None,
             me: Optional[int] = None) -> bool:
        p = self.prof[pid]
        if idx not in p["indexes"] or idx + p["memory"] > self.n_mem:
            return False
        if (g.occ if occ is None else occ) & self._span(pid, idx):
            return False
        used_me = g.me if me is None else me
        return not (p["media_extensions"] and used_me + p["media_extensions"] > self.max_me)

    def first_index(self, g: Gpu, pid: int) -> Optional[int]:
        for idx in self.order[pid]:
            if self.fits(g, pid, idx):
                return idx
        return None

    def place(self, wid: str, pid: int, gid: str, idx: int) -> None:
        g = self.gpus[gid]
        if not self.fits(g, pid, idx):
            raise ValueError(f"{gid}: profile {pid} does not fit at {idx}")
        p = self.prof[pid]
        g.placements.append((wid, pid, idx))
        g.occ |= self._span(pid, idx)
        g.mem += p["memory"]
        g.cmp += p["compute"]
        g.me += p["media_extensions"]
        self.where[wid] = gid
        self.pid_of[wid] = pid

    def remove(self, wid: str) -> Tuple[str, int, int]:
        """Unplace ``wid``; returns (gid, position in the list, index)."""
        gid = self.where.pop(wid)
        g = self.gpus[gid]
        at = next(i for i, pl in enumerate(g.placements) if pl[0] == wid)
        _, pid, idx = g.placements.pop(at)
        p = self.prof[pid]
        g.occ &= ~self._span(pid, idx)
        g.mem -= p["memory"]
        g.cmp -= p["compute"]
        g.me -= p["media_extensions"]
        return gid, at, idx

    def _unremove(self, wid: str, pid: int, gid: str, at: int, idx: int) -> None:
        self.place(wid, pid, gid, idx)
        g = self.gpus[gid]
        g.placements.insert(at, g.placements.pop())

    def util(self, g: Gpu) -> int:
        """Joint slice utilization times (memory + GPU slices)."""
        return g.mem + g.cmp

    def used(self) -> List[Gpu]:
        """GPUs that hold a placement."""
        return [self.gpus[g] for g in self.gids if self.gpus[g].placements]

    def n_used(self) -> int:
        return sum(1 for g in self.gpus.values() if g.placements)

    def layout(self) -> Dict[str, Tuple[Tuple[str, int, int], ...]]:
        return {gid: tuple(sorted(g.placements))
                for gid, g in self.gpus.items() if g.placements}

    # ---- rule-based deploy (Sec 4.2) ---------------------------------------
    def max_util_spot(self, pid: int, candidates: Optional[Sequence[str]] = None,
                      allow_new: bool = True) -> Spot:
        best = None
        for gid in (self.gids if candidates is None else candidates):
            g = self.gpus[gid]
            if candidates is None and not g.placements:
                continue  # used GPUs first; a free GPU is the fallback
            idx = self.first_index(g, pid)
            if idx is None:
                continue
            u = self.util(g)
            if best is None or u > best[0] or (u == best[0] and gid < best[1]):
                best = (u, gid, idx)
        if best is not None:
            return best[1], best[2]
        if allow_new and candidates is None:
            for gid in self.gids:
                g = self.gpus[gid]
                if not g.placements:
                    idx = self.first_index(g, pid)
                    if idx is not None:
                        return gid, idx
        return None

    def deploy(self, workloads: Sequence[Tuple[str, int]]) -> Dict[str, Spot]:
        """Place (wid, profile id) pairs; returns wid -> (gid, index) or None."""
        out: Dict[str, Spot] = {}
        for wid, pid in sorted(workloads, key=lambda w: (self.rank[w[1]], w[0])):
            spot = self.max_util_spot(pid)
            if spot is not None:
                self.place(wid, pid, *spot)
            out[wid] = spot
        return out

    # ---- compaction (Sec 4.2) -----------------------------------------------
    def _undo(self, journal: List[tuple]) -> None:
        for op in reversed(journal):
            if op[0] == "place":
                self.remove(op[1])
            else:
                _, wid, pid, gid, at, idx = op
                self._unremove(wid, pid, gid, at, idx)

    def _vacate(self, gid: str, targets: Sequence[str], journal: List[tuple]) -> bool:
        """Empty ``gid`` into ``targets`` with one-shot moves, or undo."""
        targets = [t for t in targets if t != gid]
        before: Dict[str, Tuple[int, int]] = {}  # first-touch (occ, me)
        mark = len(journal)
        moves = []
        victims = sorted(self.gpus[gid].placements, key=lambda pl: self.rank[pl[1]])
        for wid, pid, _ in victims:
            _, at, idx = self.remove(wid)
            journal.append(("remove", wid, pid, gid, at, idx))
            spot = self.max_util_spot(pid, candidates=targets, allow_new=False)
            if spot is None:
                self._undo(journal[mark:])
                del journal[mark:]
                return False
            g = self.gpus[spot[0]]
            before.setdefault(spot[0], (g.occ, g.me))
            self.place(wid, pid, *spot)
            journal.append(("place", wid))
            moves.append((wid, pid, spot))
        for wid, pid, (dst, idx) in moves:
            occ, me = before[dst]
            if not self.fits(self.gpus[dst], pid, idx, occ=occ, me=me):
                self._undo(journal[mark:])
                del journal[mark:]
                return False
        return True

    def _free_slices(self, g: Gpu) -> int:
        """Free GPU slices, plus one when the extra memory position is free."""
        n = sum(1 for i in range(self.n_gpu) if not (g.occ >> i) & 1)
        return n + (0 if (g.occ >> (self.n_mem - 1)) & 1 else 1)

    def compact(self) -> None:
        progress = True
        while progress:
            progress = False
            used = sorted(self.used(), key=lambda g: (self.util(g), g.gid))
            free_total = sum(self._free_slices(g) for g in used)
            for g in used:
                need = sum(self.prof[pid]["memory"] for _, pid, _ in g.placements)
                if free_total - self._free_slices(g) < need:
                    continue
                others = [o.gid for o in used if o is not g]
                if self._vacate(g.gid, others, []):
                    progress = True
                    break
            if progress:
                continue
            free = [gid for gid in self.gids if not self.gpus[gid].placements]
            if not free:
                continue
            borrowed = free[0]
            journal: List[tuple] = []
            vacated = 0
            for g in sorted(self.used(), key=lambda g: (self.util(g), g.gid)):
                targets = [o.gid for o in self.used() if o is not g] + [borrowed]
                if self._vacate(g.gid, targets, journal):
                    vacated += 1
            if vacated > 1:
                progress = True
            else:
                self._undo(journal)


def initial_fleet(cfg: dict, numeric_indexes: bool = False) -> Tuple[Fleet, List[str]]:
    """The Sec-5.1 fleet snapshot of ``cfg['fleet_seed']``; returns the fleet
    and its workload ids in the order they were made."""
    fleet = Fleet(cfg, numeric_indexes)
    rng = np.random.default_rng(cfg["fleet_seed"])
    n_alloc = int(round(cfg["n_gpus"] * cfg["allocated_fraction"]))
    alloc = list(rng.choice(fleet.gids, size=n_alloc, replace=False))
    lo, hi = cfg["fill_utilization"]
    denom = fleet.n_mem + fleet.n_gpu
    wids: List[str] = []
    for gid in alloc:
        g = fleet.gpus[gid]
        target = rng.uniform(lo, hi)
        attempts = 0
        while fleet.util(g) / denom < target and attempts < cfg["fill_attempts"]:
            pid = int(rng.choice(cfg["profile_pool"]))
            # the generator fills in Table-1 preference order in every variant
            idx = next((i for i in fleet.prof[pid]["indexes"]
                        if fleet.fits(g, pid, i)), None)
            if idx is None:
                attempts += 1
                continue
            wid = f"w{len(wids)}"
            fleet.place(wid, pid, gid, idx)
            wids.append(wid)
    return fleet, wids

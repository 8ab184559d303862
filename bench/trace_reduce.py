"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
compiled program and per operation, and the longest idle gaps, each named
by the harness span the host was in.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU every chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation and whose line ``XLA Modules`` holds one event per execution of a
compiled program.  Busy time is the union of a chip's operation intervals.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def is_tpu_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _program(name: str) -> str:
    """A program's name without its per-compilation id: ``jit__decode(12)``."""
    return re.sub(r"\(\d+\)$", "", name)


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(path: str, span_names: Sequence[str],
                 device_plane: Callable[[str], bool] = is_tpu_plane,
                 ops_line: Callable[[str], bool] = lambda n: n == "XLA Ops",
                 modules_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
                 top: int = 10) -> Optional[dict]:
    """Busy seconds (mean over device planes), seconds and executions per
    program and seconds per operation (mean over planes), and the ``top`` longest idle gaps of the
    first plane between its first and last operation, each named by the
    host span (one of ``span_names``) that overlaps it most, else "other".
    None when no device plane has an operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy: List[List[Interval]] = []
    programs: Dict[str, float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    spans: List[Tuple[float, float, str]] = []
    wanted = set(span_names)
    for plane in pd.planes:
        if device_plane(plane.name):
            iv: List[Interval] = []
            for line in plane.lines:
                if ops_line(line.name):
                    for ev in line.events:
                        if ev.duration_ns <= 0:
                            continue
                        iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        ops[ev.name] += ev.duration_ns * 1e-9
                elif modules_line(line.name):
                    for ev in line.events:
                        programs[_program(ev.name)] += ev.duration_ns * 1e-9
                        runs[_program(ev.name)] += 1
            if iv:
                busy.append(_union(iv))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if not busy:
        return None
    busy_s = sum(sum(b - a for a, b in u) for u in busy) / len(busy) * 1e-9
    first = busy[0]
    gaps = [(first[k][1], first[k + 1][0]) for k in range(len(first) - 1)]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    spans.sort()
    named = []
    for a, b in gaps[:top]:
        best, cover = "other", 0.0
        for s0, s1, name in spans:
            if s0 >= b:
                break
            c = min(b, s1) - max(a, s0)
            if c > cover:
                best, cover = name, c
        named.append([best, (b - a) * 1e-9])
    n = len(busy)
    return {
        "n_devices": n,
        "busy_s": busy_s,
        "programs": {k: v / n for k, v in programs.items()},
        "program_runs": {k: v / n for k, v in runs.items()},
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        "idle_gaps": named,
    }

"""Fabric: device time of one batched sweep over every (GPU, profile,
index) triple, the feasibility and score programs of ``core/fabric.py``,
mean over their executions in the trace."""

PROGRAMS = ("_feasible_kernel", "_score_kernel")


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "placement" or not tr:
        return None
    runs = [(s, tr["program_runs"][name]) for name, s in tr["programs"].items()
            if any(p in name for p in PROGRAMS)]
    n = sum(k for _, k in runs)
    return sum(s for s, _ in runs) / n * 1e3 if n else None

"""Replica engine queue: due time to the start of the pump(1) round that
admitted the request, mean over counted requests admitted in the traced
part of the window (writing the trace out stalls the loop after it)."""


def read(rec):
    if rec["kind"] != "served":
        return None
    t0, t1 = rec["trace_interval"]
    w = [r["admit_start"] - r["due"] for r in rec["requests"] if r["admit_start"] >= 0
         and (t0 is None or t0 <= r["admit_start"] <= t1)]
    return sum(w) / len(w) * 1e3 if w else None

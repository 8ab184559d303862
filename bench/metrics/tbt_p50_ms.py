"""Time between tokens, median over every gap between successive token
deliveries of every counted request (tokens that reach the host together
make one delivery)."""
import numpy as np


def read(rec):
    if rec["kind"] != "served":
        return None
    gaps = [b[0] - a[0] for r in rec["requests"]
            for a, b in zip(r["deliveries"], r["deliveries"][1:])]
    return float(np.median(gaps)) * 1e3 if gaps else None

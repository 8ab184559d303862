"""Cluster server: host wall time of the pump(1) rounds that admitted no
request (decode only), median over the traced part of the window."""
import numpy as np


def read(rec):
    if rec["kind"] != "served":
        return None
    t0, t1 = rec["trace_interval"]
    d = [r[1] - r[0] for r in rec["rounds"]
         if r[2] == 0 and (t0 is None or (r[0] >= t0 and r[1] <= t1))]
    return float(np.median(d)) * 1e3 if d else None

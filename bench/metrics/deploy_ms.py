"""Placement engine: host wall time of one deploy call, median over the
window's arrivals."""
import numpy as np


def read(rec):
    if rec["kind"] != "placement" or not rec["deploy_s"]:
        return None
    return float(np.median(rec["deploy_s"])) * 1e3

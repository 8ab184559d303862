"""Time from a placement request's due time to the return of the deploy
that placed it, 95th percentile over every arrival of the window; an
arrival due during a compaction waits for it."""
import numpy as np


def read(rec):
    if rec["kind"] != "placement" or not rec["place_s"]:
        return None
    return float(np.percentile(rec["place_s"], 95)) * 1e3

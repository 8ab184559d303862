"""Placement engine: host wall time of one compact call, mean over the
window's compactions."""


def read(rec):
    if rec["kind"] != "placement" or not rec["compact_s"]:
        return None
    return sum(rec["compact_s"]) / len(rec["compact_s"]) * 1e3

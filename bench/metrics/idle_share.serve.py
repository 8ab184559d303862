"""Device: share of the traced part of the window in which no operation ran
on the chip (1 - busy / traced time, busy the union of operation intervals)."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "served" or not tr or rec["traced_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / rec["traced_s"])

"""Time to first token, 90th percentile over every counted request: from
its due time to the end of the round whose tokens first reached the host.
A request that never got a token counts at the end of the drain."""
import numpy as np


def read(rec):
    if rec["kind"] != "served":
        return None
    end = max(r[1] for r in rec["rounds"]) if rec["rounds"] else rec["seconds"]
    t = [(r["deliveries"][0][0] if r["deliveries"] else end) - r["due"]
         for r in rec["requests"]]
    return float(np.percentile(t, 90)) * 1e3
